"""Fused Pallas multi-tensor optimizer apply — one HBM pass per step.

Parity target: the reference's multi-tensor fused Adam
(``csrc/adam/multi_tensor_adam.cu:123``) — ONE kernel pass per chunk that
reads grad+param+m+v and writes param+m+v, with the chunked multi-tensor
front end amortizing thousands of small leaves into a handful of launches.

Why this exists on TPU at all (given XLA already fuses elementwise ops):
XLA fuses *within* a leaf, but the optax apply is still one fusion per
param leaf — ~450 kernel launches for an unrolled GPT-2, each re-paying
launch + pipeline-warmup overhead — and the engine's clip multiply,
unscale, bias correction and stochastic-rounding write are separate
HBM passes when XLA's fusion heuristics split them. The Pallas kernel
makes the single-pass property structural instead of heuristic.

Two entry points share the kernel:

- ``fused_apply`` (PR-1 API, kept verbatim): the caller has already
  resolved the clip coefficient and the overflow vote; the kernel folds
  the coefficient into its grad read. The engine's historical "two-pass"
  path: a separate full-tree norm read precedes the apply.
- ``fused_step`` (the one-pass path): the global-norm reduction, fp16
  unscale, overflow vote, clip, overflow-skip select, and the
  compute-dtype cast-cache refresh ALL ride inside the fused pass:

      kernel 1 (per chunk): sq-norm partials of the packed grads
                            (+ one XLA reduction per in-place leaf)
      scalar carry:         norm = sqrt(psum partials) / scale
                            overflow = !isfinite(norm)   [fp16]
                            coeff = min(1, clip/(norm+1e-6))
      kernel 2 (per chunk / per in-place leaf):
                            read g,p,m,v; g = (g*inv)*coeff
                            m',v' Adam update (f32 moments)
                            skip-select (overflow holds the step)
                            write p' (+ optional compute-dtype cast copy,
                            + optional in-kernel bf16 stochastic round)

  so optimizer state (param+m+v) is read and written exactly ONCE per
  step: no separate norm pass, no full-tree unscale multiply, no
  post-apply jnp.where overflow select, no post-apply cast pass.

The plan: in place or packed
----------------------------

Where a leaf's moments live, and how the kernel reaches the leaf, is
decided per float leaf from its SHAPE alone (``update_plan``; no option,
no model name):

- **In place.** A leaf of at least ``_INPLACE_MIN_ELEMS`` (2**19)
  elements whose collapsed 2-D view ``[prod(leading dims), last dim]``
  is a bitcast on the TPU — last dim a multiple of 128 lanes, the row
  count and the second-to-last dim multiples of the widest sublane tile
  (32, whatever the dtype) — is updated WHERE IT LIES. Its moments are
  f32 arrays of the leaf's own shape (``FusedAdamState.leaf_m/leaf_v``),
  ZeRO-sharded like the leaf's gradient (zero/partition.py's
  first-divisible-dim rule; stage 3's spec under ZeRO-3). The kernel
  reads the gradient AT THE WIDTH IT ARRIVES (bf16 from a master-free
  one-device backward, f32 from every path that summed it: dp > 1, the
  accumulation scan, 1F1B, the trio), the parameter and both moments
  through the 2-D view with ``input_output_aliases`` on parameter and
  moments: nothing is copied, concatenated, relaid or widened in HBM —
  the kernel's first line widens in registers, which is exact. The
  squared norm of these leaves is a plain reduction per leaf over the
  WIDENED values (XLA fuses the convert into it and runs it at
  bandwidth).
- **Packed.** Every other float leaf (biases, LayerNorm vectors,
  anything small or off the tiling) goes through ONE flat group buffer
  per dtype with its own flat moments — the layout below. For the
  scanned GPT-2 that is 0.6M of gpt2-large's 774M elements; gpt2-xl
  (width 1600, off the 128-lane tiling) keeps all but one of its
  matrices there and is no slower than before the plan.

Assembling flat gradient and parameter buffers for the large leaves was
63-72 ms of gpt2-large's 347 ms step on a v5e around a 28 ms kernel
(PERF.md, PR 24-26); the plan removes it.

**Start-up rule: one lowered program per leaf geometry.** Pallas lowers a
kernel to Mosaic while JAX lowers the step — before the compile-cache
key exists, so on EVERY process start, cached executable or not. Each
``pallas_call`` site costs 30-50 ms of host work (kernel body traced,
lowered, serialised; PERF.md, PR 26). ``_update_leaf`` is therefore an
inner ``jit``: traced and lowered once per distinct (shape, dtype,
options), called from every leaf of that geometry — six programs for the
scanned GPT-2's six matrices, not one per layer for an unrolled model.
The in-place norm needs no kernel at all, and the plan, the state and
the checkpoint-layout check run no per-leaf device work outside the
engine's one start-up ``jit``.

The checkpoint layout tag (``fused_moment_layout`` in engine_meta.json)
is 3 for this state; 2 (every leaf in the flat buffers) and 1
(end-to-end concatenation) are refused at load.

Packed-group layout (V-interleaved, ZeRO-shard-local)
-----------------------------------------------------

The PACKED leaves flatten into contiguous same-dtype buffers.
PR-1 concatenated leaves end to end, which made every per-device flat
chunk a FULL-tree buffer under ZeRO sharding (GSPMD gathered the
dp-sharded moments around the opaque kernel — COMM_AUDIT.json's
``fused_chunk_gather`` finding). The layout is now *virtual-shard
interleaved*: each leaf is padded to a multiple of ``V`` virtual shards
and reshaped to ``[V, r_leaf]``; leaves concatenate along axis 1 into a
``[V, L]`` group buffer (stored flat as ``[V*L]``). Row v holds the
v-th 1/V slice of every leaf, so:

- a contiguous 1/dp range of the flat buffer == ``V/dp`` whole rows ==
  the dp-shard of every packed leaf (any dp dividing V);
- the kernels run under ``shard_map`` over the dp axis on LOCAL rows
  (and on each in-place leaf's own dp shard) — the moments are never
  gathered, each device updates exactly its ZeRO shard, and the updated
  params leave the region dp-sharded (the engine's replicated
  out_shardings turn that into the per-leaf ZeRO-2 param all-gather);
- neither layout depends on dp (``V`` is a constant 8, widened to dp
  only above 8 devices; in-place moments have the leaf's shape), so
  checkpoints stay elastic across dp resizes exactly like PR-1's;
- under ZeRO-3 (params THEMSELVES dp-sharded, runtime/zero/stage3.py)
  the apply needs NO new gather: a leaf sharded on its leading dim over
  dp owns contiguous flat ranges, which are exactly whole virtual rows
  (``V/dp`` rows = the d-th 1/dp of every leaf), so the
  ``_flatten_group`` row constraint is a local reshape and the kernels
  consume grad, param AND moments as the same dp shard — verified by
  COMM_AUDIT.json's zero3 config (zero apply-time collectives). Packed
  leaves the stage-3 layer scan shards on a non-leading dim relayout at
  region entry (still 1/dp per device, never a gather to full);
  in-place leaves enter by their stage-3 spec and relayout nothing.

The deterministic math is bit-exact with ``optax.adamw`` / the engine's
coupled-Adam chain: every multiply-add is written in optax's association
order (see ``tests/test_fused_update.py``). The one-pass norm is the
same sum-of-squares at a different association (chunk partials of the
packed group, one sum per in-place leaf), so clip coefficients agree to
f32 ulp — the same
cross-program tolerance class PR-1 documented for FMA contraction.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

try:  # TPU backend bits are importable everywhere; interpret=True runs on CPU
    from jax.experimental.pallas import tpu as pltpu
except Exception:  # pragma: no cover
    pltpu = None

from . import autotune
from .counter_hash import hash_u32

ScheduleOrFloat = Union[Callable, float]

# Kernel geometry: blocks of up to _R sublane rows x _W = 128 lanes. The
# lane width is exactly ONE vreg row on purpose: a flat f32/bf16 buffer
# (1-D tile of 1024 contiguous elements) and its [n/128, 128] view (one
# (8, 128) tile = 8 full rows = the same 1024 contiguous elements) share
# a memory order, so the reshape between the stored flat moment buffers
# and the kernel's 2-D view is a BITCAST and the in-place aliasing holds.
# A wider view ([n/1024, 1024]) is a physical relayout on the TPU: XLA
# then copies m and v in AND out every step (+8.6 GB of live temps at
# gpt2-large — the step no longer fits a 16 GB chip; measured with the
# chip's compiler, tools/compile_rehearsal.py). One (1024, 128) f32
# block is 512 KiB; with 4 inputs + up to 4 outputs double buffered that
# is ~8 MiB of VMEM — inside the ~16 MiB/core budget.
_W = 128
_R = 1024
# Group rows pad to a multiple of 8192 elements so the per-shard row
# count is always a multiple of the f32 minimum sublane tile (8) at any
# kernel lane width up to 1024 (the moment-buffer layout — part of the
# checkpoint format — does not depend on _W).
_ROW_QUANTUM = 8192

# Virtual shard count: the flat layout interleaves every leaf over _V
# rows, so any dp <= _V owns whole rows (= contiguous flat ranges) and
# the layout itself never depends on the live dp size (checkpoint
# elasticity). Meshes wider than _V widen V to dp — sizes above 8 are
# beyond this repo's test envelope and noted in docs/tutorials/kernels.md.
_V = 8


class FusedAdamState(NamedTuple):
    """Fused optimizer state. In-place leaves (``update_plan``) keep f32
    moments of their own shape in ``leaf_m`` / ``leaf_v``, in leaf order,
    ZeRO-sharded like the leaf's gradient. Every other float leaf shares
    one flat f32 moment buffer per dtype group, ``m`` / ``v``, in the
    V-interleaved layout (module docstring): ZeRO shardings
    (zero/partition.py) split the flat axis over dp, any dp dividing V
    lands on whole virtual rows. Neither layout depends on dp, so
    checkpoint shards stay elastic across dp resizes."""
    count: jax.Array                 # int32 scalar, number of updates
    m: Tuple[jax.Array, ...]
    v: Tuple[jax.Array, ...]
    leaf_m: Tuple[jax.Array, ...] = ()
    leaf_v: Tuple[jax.Array, ...] = ()


class FusedStepOut(NamedTuple):
    """Everything the one-pass ``fused_step`` produces."""
    params: Any
    state: "FusedAdamState"
    cast_params: Any                 # compute-dtype copy (None when unused)
    grad_norm: jax.Array             # unscaled global norm (-1.0 = skipped)
    overflow: jax.Array              # bool (False when not fp16)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def virtual_shards(dp: int = 1) -> int:
    return max(_V, int(dp))


def _float_groups(leaves):
    """Deterministic dtype-grouping of float leaves: [(dtype, [leaf idx])],
    sorted by dtype name. Non-float leaves bypass the kernel entirely."""
    groups = {}
    for i, leaf in enumerate(leaves):
        if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype,
                                                     jnp.floating):
            groups.setdefault(jnp.dtype(leaf.dtype), []).append(i)
    return sorted(groups.items(), key=lambda kv: kv[0].name)


def _leaf_rows(n: int, shards: int) -> int:
    """Per-virtual-shard row length of a leaf (leaf padded to V|n)."""
    return -(-int(n) // shards)


def _group_row_len(sizes, shards: int) -> int:
    """Padded per-row length L of a group buffer: sum of leaf rows,
    padded so every 1/V row is a whole number of _ROW_QUANTUM elements."""
    L = sum(_leaf_rows(n, shards) for n in sizes)
    return max(_ROW_QUANTUM, -(-L // _ROW_QUANTUM) * _ROW_QUANTUM)


def group_nbytes(sizes, shards: int = _V, itemsize: int = 4) -> int:
    """Padded group-buffer bytes (one moment buffer) — the analytic
    footprint tools use."""
    return virtual_shards(shards) * _group_row_len(sizes, shards) * itemsize


# --------------------------------------------------------------------- #
# The plan: which leaves are updated where they lie
# --------------------------------------------------------------------- #
# A leaf is updated IN PLACE when its collapsed 2-D view
# [prod(leading dims), last dim] is a bitcast on the TPU — last dim a
# whole number of 128-lane vregs, and the rows that collapse into one
# another (and the row count itself) whole sublane tiles. The tile is
# taken at its widest (32 rows: 8-bit; bf16 needs 16, f32 8) whatever
# the leaf's dtype, so the plan — and with it the moments' layout, a
# checkpoint format — is the same for a model kept in f32 and in bf16
# (the master-free engine creates its state from an f32 view).
_ROW_TILE = 32
# ... and large enough that one more kernel launch is noise: at 2**19
# elements the update moves 11.5 MB (22 B an element), 14 us at the
# v5e's 819 GB/s, against a few us of launch and pipeline warm-up. Every
# GPT-2 matrix is over 1.0M elements (wpe of gpt2-medium: 1024 x 1024),
# every bias and LayerNorm leaf of the scanned model under 0.2M. What
# the threshold also buys is START-UP: each distinct in-place geometry
# is one more pallas_call to trace and lower on every process start.
_INPLACE_MIN_ELEMS = 1 << 19
# Block budget of the in-place kernel, in elements: the packed kernel's
# (1024, 128) block. Views wider than _MAX_COLS are cut in columns too.
_BLOCK_ELEMS = _R * _W
_MAX_COLS = 4096


def _in_place(shape, dtype) -> bool:
    if not jnp.issubdtype(dtype, jnp.floating) or len(shape) < 2:
        return False
    n = 1
    for d in shape:
        n *= int(d)
    if n < _INPLACE_MIN_ELEMS or shape[-1] % _W:
        return False
    return (n // shape[-1]) % _ROW_TILE == 0 and \
        (len(shape) == 2 or shape[-2] % _ROW_TILE == 0)


class UpdatePlan(NamedTuple):
    """Where each float leaf of a parameter tree is updated — a pure
    function of the leaves' shapes (``update_plan``)."""
    inplace: Tuple[int, ...]        # leaf indices updated where they lie
    packed: Tuple[Tuple[Any, Tuple[int, ...]], ...]   # (dtype, leaf idxs)


def update_plan(leaves) -> UpdatePlan:
    """In place / packed, per float leaf (see ``_in_place``); packed
    leaves group by dtype as ``_float_groups`` orders them."""
    inplace = tuple(
        i for i, leaf in enumerate(leaves)
        if hasattr(leaf, "dtype") and _in_place(leaf.shape, leaf.dtype))
    taken = set(inplace)
    rest = [None if i in taken else leaf for i, leaf in enumerate(leaves)]
    return UpdatePlan(inplace, tuple(
        (dt, tuple(idxs)) for dt, idxs in _float_groups(rest)))


def plan_summary(params: Any) -> Dict[str, Any]:
    """What the engine reports at start: leaves and optimizer bytes
    (parameter + both f32 moments) in place and packed, and the number
    of distinct Adam kernel programs the step lowers."""
    leaves = jax.tree_util.tree_leaves(params)
    plan = update_plan(leaves)

    def nbytes(idxs):
        return sum(int(leaves[i].size) *
                   (jnp.dtype(leaves[i].dtype).itemsize + 8) for i in idxs)
    packed = [i for _, idxs in plan.packed for i in idxs]
    b_in, b_pk = nbytes(plan.inplace), nbytes(packed)
    return {
        "leaves_in_place": len(plan.inplace), "bytes_in_place": b_in,
        "leaves_packed": len(packed), "bytes_packed": b_pk,
        "share_in_place": b_in / max(1, b_in + b_pk),
        "kernel_programs": len(plan.packed) + len(
            {(tuple(leaves[i].shape), jnp.dtype(leaves[i].dtype))
             for i in plan.inplace}),
    }


def _flat_1d(x: jax.Array) -> jax.Array:
    """``x.reshape(-1)`` pinned as a real 1-D intermediate.

    XLA merges consecutive reshapes, and the merged relayouts this
    module would otherwise ask for — leaf [a, b] <-> [V, r], [V, L] <->
    [rows, _W] with r, L in the millions — cost the TPU compiler time
    PROPORTIONAL to the array (13-29 s per 64M elements against a
    described v5e; gpt2-large's step had not compiled after 25 min).
    Either half through 1-D compiles in well under a second; the barrier
    keeps XLA from fusing the halves back together."""
    if x.ndim == 1:
        return x
    return lax.optimization_barrier(x.reshape(-1))


def _padded_flat(leaf, dtype, shards: int, pin: bool):
    """(1-D leaf in ``dtype``, zero-padded to ``shards * r``; r).
    ``pin`` keeps the 1-D form a real intermediate (``_flat_1d``) — for
    when the next op is another reshape XLA would merge it with."""
    f = (_flat_1d(leaf) if pin else leaf.reshape(-1)).astype(dtype)
    r = _leaf_rows(f.size, shards)
    if r * shards > f.size:
        f = jnp.concatenate([f, jnp.zeros((r * shards - f.size,), dtype)])
    return f, r


@jax.named_scope("flatten")
def _flatten_group(leaves, idxs, dtype, shards: int, Lpad: int,
                   constrain=None) -> jax.Array:
    """Leaves -> the V-interleaved group buffer (row v = the v-th 1/V
    slice of every leaf, then the pad).

    Sharded (``constrain`` = the NamedSharding pinning rows to the dp
    axis): a ``[shards, Lpad]`` array — each leaf reshapes to
    [shards, r_leaf] and the rows concatenate along axis 1; the concat
    axis is NOT the sharded axis, so GSPMD partitions the assembly
    row-locally (no full-buffer materialization; the per-leaf reshard is
    bounded by that leaf's size).

    Unsharded: the SAME element order assembled directly as the flat
    ``[shards * Lpad]`` buffer the kernels read, from 1-D slices — no
    [V, L] intermediate, whose relayout to 1-D is one more full pass
    over the buffer (2.88 GB of f32 grads at gpt2-large): on the v5e
    those passes were 250 ms of a 557 ms step, and under gradient
    accumulation put the step over a 16 GB chip (PERF.md, PR 21)."""
    flats = [_padded_flat(leaves[i], dtype, shards,
                          pin=constrain is not None) for i in idxs]
    L = sum(r for _, r in flats)
    if constrain is None:
        tail = [jnp.zeros((Lpad - L,), dtype)] if Lpad > L else []
        rows = [piece for v in range(shards)
                for piece in [lax.slice(f, (v * r,), ((v + 1) * r,))
                              for f, r in flats] + tail]
        return jnp.concatenate(rows) if len(rows) > 1 else rows[0]
    cols = [lax.with_sharding_constraint(f.reshape(shards, r), constrain)
            for f, r in flats]
    if Lpad > L:
        cols.append(lax.with_sharding_constraint(
            jnp.zeros((shards, Lpad - L), dtype), constrain))
    buf = jnp.concatenate(cols, axis=1) if len(cols) > 1 else cols[0]
    return lax.with_sharding_constraint(buf, constrain)


@jax.named_scope("unflatten")
def _unflatten_group(buf: jax.Array, like_leaves, idxs,
                     shards: int) -> Dict[int, jax.Array]:
    """Group buffer -> {leaf idx: leaf-shaped array}. ``buf`` is the
    ``[shards, Lpad]`` array (slices stay on the sharded-safe row axis;
    each leaf re-gathers at most its own size downstream) or the flat
    ``[shards * Lpad]`` form (1-D slices, no 2-D intermediate)."""
    out: Dict[int, jax.Array] = {}
    off = 0
    Lpad = buf.size // shards
    for i in idxs:
        n = int(like_leaves[i].size)
        r = _leaf_rows(n, shards)
        if buf.ndim == 1:
            piece = jnp.concatenate(
                [lax.slice(buf, (v * Lpad + off,), (v * Lpad + off + r,))
                 for v in range(shards)])
        else:
            piece = _flat_1d(lax.slice(buf, (0, off), (shards, off + r)))
        out[i] = piece[:n].reshape(like_leaves[i].shape)
        off += r
    return out


def leaf_moment_views(state: "FusedAdamState", params: Any,
                      shards: int = _V) -> Tuple[Any, Any]:
    """Per-leaf views of the fused moments (tests / debugging): returns
    (m_tree, v_tree) shaped like ``params``' float leaves (None at
    non-float positions), whichever way the plan keeps each leaf's."""
    p_leaves, treedef = jax.tree_util.tree_flatten(params)
    shards = virtual_shards(shards)
    plan = update_plan(p_leaves)
    m_out: List[Any] = [None] * len(p_leaves)
    v_out: List[Any] = [None] * len(p_leaves)
    for k, i in enumerate(plan.inplace):
        m_out[i], v_out[i] = state.leaf_m[k], state.leaf_v[k]
    for gi, (dt, idxs) in enumerate(plan.packed):
        Lpad = _group_row_len([p_leaves[i].size for i in idxs], shards)
        m2 = state.m[gi].reshape(shards, Lpad)
        v2 = state.v[gi].reshape(shards, Lpad)
        for i, a in _unflatten_group(m2, p_leaves, idxs, shards).items():
            m_out[i] = a
        for i, a in _unflatten_group(v2, p_leaves, idxs, shards).items():
            v_out[i] = a
    return (jax.tree_util.tree_unflatten(treedef, m_out),
            jax.tree_util.tree_unflatten(treedef, v_out))


# --------------------------------------------------------------------- #
# Kernels
# --------------------------------------------------------------------- #
def _sqnorm_kernel(g_ref, out_ref):
    """Per-chunk squared-norm partial: replaces the separate full-tree
    ``global_norm`` read (and, via isfinite(norm), the full-tree
    ``tree_has_inf_or_nan`` read) of the two-pass path. Pad regions are
    zero by construction and contribute nothing."""
    g = g_ref[...].astype(jnp.float32)
    s = jnp.sum(g * g)
    out_ref[...] = jnp.broadcast_to(s, out_ref.shape)


def _fused_adam_kernel(scal_ref, seed_ref, g_ref, p_ref, m_ref, v_ref,
                       *out_refs, b1: float, b2: float, eps: float,
                       wd: float, coupled: bool, use_inv: bool,
                       use_coeff: bool, one_pass: bool, sr: bool,
                       cast: bool, out_dtype, cast_dtype, ncols: int):
    """One block of the fused apply (grid: row blocks x column blocks
    of a 2-D view ``ncols`` wide).

    scal_ref (SMEM, f32 [1,8]): [neg_lr, bias_corr1, bias_corr2, coeff,
    inv_scale, skip, 0, 0]; seed_ref (SMEM, int32 [1,2]): [sr seed,
    base element index]. Math follows optax's association order
    exactly (bit parity on the deterministic path); the fp16 unscale and
    the clip multiply are SEPARATE multiplies, preserving the historical
    ``(g*inv)*coeff`` association of the two-pass engine path."""
    p_out = out_refs[0]
    m_out, v_out = out_refs[1], out_refs[2]
    cast_out = out_refs[3] if cast else None
    g = g_ref[...].astype(jnp.float32)
    if use_inv:
        g = g * scal_ref[0, 4]
    if use_coeff:
        g = g * scal_ref[0, 3]
    p32 = p_ref[...].astype(jnp.float32)
    if coupled and wd:
        # Classic (coupled L2) Adam: decay folded into the gradient
        # BEFORE the moment update (optax.add_decayed_weights first in
        # the chain; reference FusedAdam adam_w_mode=False).
        g = g + wd * p32
    m = (1 - b1) * g + b1 * m_ref[...]
    v = (1 - b2) * (g * g) + b2 * v_ref[...]
    u = (m / scal_ref[0, 1]) / (jnp.sqrt(v / scal_ref[0, 2]) + eps)
    if (not coupled) and wd:
        u = u + wd * p32
    new_p = p32 + u * scal_ref[0, 0]
    if one_pass:
        # Overflow-skip folded into the pass: the old params/moments are
        # already in VMEM, so holding the step costs a register select
        # instead of the engine's post-apply full-tree jnp.where pass.
        keep_old = scal_ref[0, 5] > 0.0
        new_p = jnp.where(keep_old, p32, new_p)
        m = jnp.where(keep_old, m_ref[...], m)
        v = jnp.where(keep_old, v_ref[...], v)
    m_out[...] = m
    v_out[...] = v
    if cast:
        cast_out[...] = new_p.astype(cast_dtype)
    if sr:
        # In-kernel unbiased stochastic rounding to bf16 (the master-free
        # mode): add uniform 16-bit noise to the f32 mantissa tail, then
        # truncate — E[round(x)] == x (see ops/stochastic_rounding.py).
        # Noise comes from a counter hash of the GLOBAL element index
        # (seed_ref[0,1] carries the shard's base offset), so it costs
        # zero HBM traffic and is reproducible per (seed, index).
        R, W = new_p.shape
        rows = lax.broadcasted_iota(jnp.uint32, (R, W), 0)
        cols = lax.broadcasted_iota(jnp.uint32, (R, W), 1)
        idx = seed_ref[0, 1].astype(jnp.uint32) + \
            (pl.program_id(0).astype(jnp.uint32) * jnp.uint32(R) + rows) \
            * jnp.uint32(ncols) + \
            pl.program_id(1).astype(jnp.uint32) * jnp.uint32(W) + cols
        noise = hash_u32(idx ^ seed_ref[0, 0].astype(jnp.uint32)) \
            & jnp.uint32(0xFFFF)
        bits = lax.bitcast_convert_type(new_p, jnp.uint32)
        rounded = (bits + noise) & jnp.uint32(0xFFFF0000)
        out = lax.bitcast_convert_type(rounded, jnp.float32) \
            .astype(jnp.bfloat16)
        # inf/nan must stay put (the carry could walk an inf into nan
        # space); overflow handling belongs to the loss-scale machinery.
        p_out[...] = jnp.where(jnp.isfinite(new_p), out,
                               new_p.astype(jnp.bfloat16))
    else:
        p_out[...] = new_p.astype(out_dtype)


def _on_tpu() -> bool:
    return pltpu is not None and jax.default_backend() == "tpu"


def _smem_spec(shape):
    if _on_tpu():
        return pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.BlockSpec(shape, lambda *_: (0, 0))


def _block_spec(rb: int, cb: int):
    kw = dict(memory_space=pltpu.VMEM) if _on_tpu() else {}
    return pl.BlockSpec((rb, cb), lambda i, j: (i, j), **kw)


def _chunk_spec(rb: int):
    kw = dict(memory_space=pltpu.VMEM) if _on_tpu() else {}
    return pl.BlockSpec((rb, _W), lambda i: (i, 0), **kw)


def _block_rows(rows: int, kernel: str = None, runner=None) -> int:
    """Largest power-of-two row count <= _R dividing ``rows`` (rows is
    always a multiple of 8 by the _ROW_QUANTUM padding).  With ``kernel``
    set, the pick routes through ``ops.autotune`` — candidates are the
    dividing powers of two up to _R, heuristic the largest (today's
    choice bit-for-bit under DS_AUTOTUNE=0 / on CPU)."""
    rb = _R
    while rb > 8 and rows % rb:
        rb //= 2
    assert rows % rb == 0, (rows, rb)
    if kernel is not None:
        cands = autotune.pow2_candidates(8, _R, lambda c: rows % c == 0)
        measure = autotune.measure_from_runner(runner) \
            if (runner is not None and autotune.search_allowed()) else None
        rb = autotune.resolve(kernel, (rows, _W), "float32", rb, cands,
                              measure)
        assert rows % rb == 0, (rows, rb)
    return rb


@jax.named_scope("norm")
def _run_sqnorm(gflat: jax.Array, _rb: int = None) -> jax.Array:
    """Squared norm of one flat group buffer via per-chunk partials."""
    rows = gflat.size // _W

    def runner(rb_):
        return _run_sqnorm(jnp.zeros((rows * _W,), gflat.dtype), _rb=rb_)

    rb = _rb or _block_rows(rows, kernel="fused_update_sqnorm",
                            runner=runner)
    grid = rows // rb
    out = pl.pallas_call(
        _sqnorm_kernel,
        grid=(grid,),
        in_specs=[_chunk_spec(rb)],
        # [grid, 1, 128] partials: the (1, 128) block spans the array's
        # last two dims (the TPU (8, 128) tiling rule), grid dim squeezed.
        out_specs=pl.BlockSpec((None, 1, 128), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((grid, 1, 128), jnp.float32),
        name="_sqnorm_kernel",
        interpret=_interpret(),
    )(gflat.reshape(rows, _W))
    return jnp.sum(out[:, 0, 0])


@jax.named_scope("kernel")
def _run_group(gflat, pflat, m, v, scalars, seed, *, _rb: int = None,
               **static):
    """Run the fused kernel over one flat group buffer (local shard when
    shard-mapped); ``static`` are ``_adam_call``'s options. Returns
    (p_new, m_new, v_new, cast_new_or_None)."""
    rows = gflat.size // _W

    def runner(rb_):
        zeros = [jnp.zeros(a.shape, a.dtype)
                 for a in (gflat, pflat, m, v, scalars, seed)]
        return _run_group(*zeros, _rb=rb_, **static)

    rb = _rb or _block_rows(rows, kernel="fused_update_apply",
                            runner=runner)
    shape2 = (rows, _W)
    outs = _adam_call(
        gflat.reshape(shape2), pflat.reshape(shape2), m.reshape(shape2),
        v.reshape(shape2), scalars, seed, rb, _W, **static)
    outs = tuple(_flat_1d(o) for o in outs)
    return outs if static["cast"] else outs + (None,)


def _adam_call(g2, p2, m2, v2, scalars, seed, rb: int, cb: int, *,
               cast: bool, out_dtype, cast_dtype, **static):
    """The ONE ``pallas_call`` of the Adam kernel, over 2-D operands of
    one shape in blocks of ``(rb, cb)``: the packed group's
    ``[n/128, 128]`` view and every in-place leaf's collapsed view go
    through it. A ragged last block (``rb`` not dividing the rows) is
    masked by Pallas; the kernel is elementwise, so nothing leaks."""
    shape2 = p2.shape
    kernel = functools.partial(
        _fused_adam_kernel, cast=cast, out_dtype=out_dtype,
        cast_dtype=cast_dtype, ncols=shape2[1], **static)
    spec = _block_spec(rb, cb)
    out_shape = [
        jax.ShapeDtypeStruct(shape2, out_dtype),
        jax.ShapeDtypeStruct(shape2, jnp.float32),
        jax.ShapeDtypeStruct(shape2, jnp.float32),
    ]
    if cast:
        out_shape.append(jax.ShapeDtypeStruct(shape2, cast_dtype))
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(shape2[0], rb), pl.cdiv(shape2[1], cb)),
        in_specs=[_smem_spec((1, 8)), _smem_spec((1, 2)),
                  spec, spec, spec, spec],
        out_specs=[spec] * len(out_shape),
        out_shape=out_shape,
        # In-place update: p/m/v inputs alias the outputs (same
        # shape+dtype when the param dtype matches; m/v always), so the
        # kernel never holds two copies of the moments in HBM.
        input_output_aliases=(
            {3: 0, 4: 1, 5: 2} if p2.dtype == out_dtype
            else {4: 1, 5: 2}),
        name="_fused_adam_kernel",
        interpret=_interpret(),
    )(scalars, seed, g2, p2, m2, v2)


def _leaf_blocks(rows: int, cols: int) -> Tuple[int, int]:
    """(row block, column block) of an in-place leaf's 2-D view: about
    ``_BLOCK_ELEMS`` elements, whole ``_ROW_TILE`` x 128 tiles, dividing
    the view where something near the budget does (every shape the plan
    admits on one device; a dp shard may leave a ragged, masked tail)."""
    cb = cols
    if cols > _MAX_COLS and cols % _W == 0:
        cb = max(c for c in range(_W, _MAX_COLS + 1, _W) if cols % c == 0)
    target = max(_ROW_TILE, _BLOCK_ELEMS // cb // _ROW_TILE * _ROW_TILE)
    if rows <= target:
        return rows, cb             # one block spans the rows
    rb = target
    while rb > _ROW_TILE and rows % rb:
        rb -= _ROW_TILE
    if rows % rb or 2 * rb < target:
        rb = target                 # ragged tail
    return rb, cb


@functools.partial(jax.jit, static_argnames=(
    "b1", "b2", "eps", "wd", "coupled", "use_inv", "use_coeff", "one_pass",
    "sr", "cast", "out_dtype", "cast_dtype"))
def _update_leaf(g, p, m, v, scalars, seed, **static):
    """The Adam kernel over ONE leaf where it lies (its shard, under
    ``shard_map``): operands enter through the collapsed 2-D view, a
    bitcast for every leaf the plan admits, and parameter and moments
    alias their outputs. Returns (p, m, v[, cast]) in the leaf's shape.

    An inner ``jit`` on purpose: JAX traces and lowers it once per
    distinct (shape, dtype, options) and CALLS it from every leaf of that
    geometry, so an unrolled model's hundred same-shaped matrices cost
    one pallas_call's tracing and Mosaic lowering, not a hundred — host
    work that precedes the compile-cache key and is paid on every
    process start (docs/tutorials/kernels.md)."""
    shape = p.shape
    cols = int(shape[-1])
    rows = int(p.size) // cols
    rb, cb = _leaf_blocks(rows, cols)
    outs = _adam_call(
        g.reshape(rows, cols), p.reshape(rows, cols), m.reshape(rows, cols),
        v.reshape(rows, cols), scalars, seed, rb, cb, **static)
    return tuple(o.reshape(shape) for o in outs)


def apply_hbm_bytes(params: Any, *, one_pass: bool = True,
                    cast_dtype=None, fp16: bool = False,
                    clip: bool = True, grad_dtype=None) -> Dict[str, int]:
    """Analytic HBM bytes one optimizer step's APPLY phase moves, per
    replica (monitor/cost_model.py prices the apply path with this; the
    roofline record carries both modes).

    Honest accounting — only passes the historical two-pass engine
    REALLY paid are priced, and the one-pass side pays for what it
    really runs:

    - Both modes share the apply kernel's read g+p+m+v, write p+m+v
      (+ the compute-dtype cast-copy write). An in-place leaf's gradient
      is read at ``grad_dtype``'s width — the width it reaches the apply
      at; default the parameter's own, what a backward through that
      parameter writes — and a packed leaf's at 4 B (the group buffer
      flattens in f32 whatever arrives).
    - When a norm is needed (``clip`` or ``fp16``), BOTH modes re-read
      the grads once more: the two-pass path as the separate
      ``global_norm`` pass, the one-pass path as the ``_run_sqnorm``
      kernel — a wash in bytes (the one-pass win there is launches and
      the scalar plumbing, not HBM).
    - fp16 only: the two-pass path's unscale (read+write g), the
      ``tree_has_inf_or_nan`` re-read of g, and the post-apply overflow
      select (read old p+m+v, read new p+m+v, write the selection) are
      real traced passes.  For non-fp16 runs ``overflow`` was a
      compile-time constant and XLA folded the select to nothing — no
      saving is claimed there.
    - cast_dtype only: the standalone cast pass re-READS the updated
      params (the cast write itself exists in both modes).

    Consequence: the drop is ~2.5x for fp16 configs, ~1.1x for
    fp32-master + cast-cache bf16 configs, and ~1.0x for master-free
    bf16 (where the one-pass path's value is fewer launches, not fewer
    bytes) — stated plainly in docs/tutorials/kernels.md.
    """
    leaves = [l for l in jax.tree_util.tree_leaves(params)
              if hasattr(l, "dtype") and
              jnp.issubdtype(l.dtype, jnp.floating)]
    n = sum(int(l.size) for l in leaves)
    p_bytes = sum(int(l.size) * jnp.dtype(l.dtype).itemsize
                  for l in leaves)
    inplace = set(update_plan(leaves).inplace)
    g_bytes = sum(
        int(l.size) * (jnp.dtype(grad_dtype or l.dtype).itemsize
                       if i in inplace else 4)   # packed: flat f32
        for i, l in enumerate(leaves))
    mv_bytes = 2 * 4 * n                  # f32 moments
    cast_bytes = (n * jnp.dtype(cast_dtype).itemsize) if cast_dtype else 0
    kernel = g_bytes + p_bytes + mv_bytes + p_bytes + mv_bytes + cast_bytes
    need_norm = bool(clip) or fp16
    norm_read = g_bytes if need_norm else 0
    one = kernel + norm_read
    two = kernel + norm_read
    if fp16:
        two += 2 * g_bytes                # unscale: read + write g
        two += g_bytes                    # tree_has_inf_or_nan re-read
        # overflow select (REAL only under fp16): read old + new p/m/v,
        # write the selected state
        two += 3 * (p_bytes + mv_bytes)
    if cast_dtype:
        two += p_bytes                    # cast pass re-reads new params
    out = {"one_pass": one, "two_pass": two}
    out["active"] = one if one_pass else two
    out["ratio_two_over_one"] = round(two / max(1, one), 3)
    return out


def _modes(dt, sr_on: bool, cast_dtype) -> Tuple[bool, bool]:
    """(stochastic-rounding write?, separate compute-dtype cast output?)
    of a buffer of dtype ``dt`` — one rule for packed groups and in-place
    leaves. A bf16 SR write (or an equal dtype) IS the compute-dtype
    value, so no cast output then."""
    sr = sr_on and dt == jnp.dtype(jnp.bfloat16)
    return sr, (cast_dtype is not None and not sr and
                jnp.dtype(cast_dtype) != dt)


def fused_adam(learning_rate: ScheduleOrFloat, b1: float = 0.9,
               b2: float = 0.999, eps: float = 1e-8,
               weight_decay: float = 0.0, adam_w_mode: bool = True,
               mesh=None, shard_axis: Optional[str] = None,
               leaf_specs: Optional[Callable[[], Any]] = None
               ) -> "FusedGradientTransformation":
    """Build the fused-apply transformation.

    ``adam_w_mode=True`` matches ``optax.adamw`` (decoupled decay);
    ``False`` matches the engine's coupled-L2 chain (decay folded into
    the gradient before the moments).

    ``mesh`` + ``shard_axis`` (engine-provided under ZeRO stage >= 1 on
    a pure-dp mesh) run the kernels under ``shard_map`` over the dp
    axis: every packed buffer enters as its LOCAL virtual-shard rows and
    every in-place leaf as its own dp shard, the moments are never
    gathered, and the norm partials ``psum`` into the global norm.
    ``leaf_specs()`` (called when a step is traced) gives the parameter
    tree's PartitionSpecs where they are not the first-divisible-dim
    rule (ZeRO-3's, whose scanned leaves keep the layer axis whole);
    in-place leaves and their moments enter and leave the region by
    them. Without a mesh the kernels run on the full buffers (dp=1, or
    bare transform use).

    Returned object is optax-compatible (``init``/``update``) and
    carries two fused entry points: ``fused_apply`` (PR-1 API: caller
    resolves clip/overflow) and ``fused_step`` (one-pass: norm, clip,
    fp16 unscale, overflow vote+skip, cast-cache refresh all inside the
    single HBM pass — see module docstring).
    """
    sched = learning_rate if callable(learning_rate) else None
    base_lr = None if sched is not None else float(learning_rate)
    dp = int(mesh.shape[shard_axis]) if (mesh is not None and
                                         shard_axis is not None) else 1
    shards = virtual_shards(dp)
    use_shard_map = dp > 1 and shards % dp == 0

    def _row_sharding():
        if mesh is None or shard_axis is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(mesh, P(shard_axis, None))

    def _leaves(params):
        return jax.tree_util.tree_flatten(params)

    def _group_plan(p_leaves, plan):
        """[(group idx, dtype, leaf idxs, sizes, Lpad)] of the packed
        groups."""
        out = []
        for gi, (dt, idxs) in enumerate(plan.packed):
            sizes = [int(p_leaves[i].size) for i in idxs]
            out.append((gi, dt, idxs, sizes, _group_row_len(sizes, shards)))
        return out

    def init_fn(params):
        leaves, _ = _leaves(params)
        plan = update_plan(leaves)
        bufs = tuple(jnp.zeros((shards * Lpad,), jnp.float32)
                     for _, _, _, _, Lpad in _group_plan(leaves, plan))
        lm = tuple(jnp.zeros(leaves[i].shape, jnp.float32)
                   for i in plan.inplace)
        return FusedAdamState(
            count=jnp.zeros([], jnp.int32), m=bufs,
            v=jax.tree_util.tree_map(jnp.zeros_like, bufs), leaf_m=lm,
            leaf_v=jax.tree_util.tree_map(jnp.zeros_like, lm))

    def _base_scalars(count, inv_scale):
        """The scalar carry every path shares: [neg_lr, bc1, bc2, inv].
        Bit parity: these are the exact expressions optax evaluates
        (python-float ** int32 array -> f32 power; see
        optax.tree_utils.tree_bias_correction)."""
        count_inc = count + 1
        bc1 = (1 - b1 ** count_inc).astype(jnp.float32)
        bc2 = (1 - b2 ** count_inc).astype(jnp.float32)
        lr = sched(count) if sched is not None else base_lr
        neg_lr = jnp.asarray(-1.0, jnp.float32) * jnp.asarray(
            lr, jnp.float32)
        inv = jnp.asarray(1.0, jnp.float32) if inv_scale is None \
            else jnp.asarray(inv_scale, jnp.float32)
        return jnp.stack([neg_lr, bc1, bc2, inv])

    def _kernel_region(base, seed0, pre_coeff, extra_skip, gbufs, pbufs,
                       ms, vs, lgs, lps, lms, lvs, *, groups, clip, fp16,
                       use_inv, one_pass, compute_norm, has_pre_coeff,
                       use_extra_skip, group_modes, leaf_modes,
                       cast_dtype, local):
        """Norm + apply kernels over (possibly shard-local) packed group
        buffers and in-place leaves. Runs inside shard_map when
        ``local``; all inputs are then the device's own virtual rows /
        leaf shards. ``group_modes`` / ``leaf_modes`` are static
        ``(sr, cast)`` per group and ``(dtype, sr, cast, sharded)`` per
        in-place leaf, so the cast tuples' pytree shape is fixed."""
        axis = shard_axis if local else None
        if compute_norm:
            nsq = jnp.float32(0.0)
            for g in gbufs:
                nsq = nsq + _run_sqnorm(_flat_1d(g))
            with jax.named_scope("norm"):
                # In-place leaves: a plain f32 reduction per leaf, which
                # XLA runs at bandwidth — no second pallas_call per
                # geometry to trace and lower at start-up. A narrow leaf
                # is widened INSIDE the reduction (XLA fuses that; a
                # bf16 square would be another result).
                for g in lgs:
                    nsq = nsq + jnp.sum(jnp.square(g.astype(jnp.float32)))
            if axis is not None:
                nsq = lax.psum(nsq, axis)
            # norm of the UNSCALED grads: ||g*inv|| == inv * ||g||.
            grad_norm = jnp.sqrt(nsq) * base[3]
        else:
            grad_norm = jnp.asarray(-1.0, jnp.float32)
        if fp16:
            # inf/nan anywhere in the grads surfaces as a non-finite
            # sum of squares — the norm read doubles as the overflow
            # vote (reference CheckOverflow semantics, one pass).
            overflow = jnp.logical_not(jnp.isfinite(grad_norm))
        else:
            overflow = jnp.asarray(False)
        if use_extra_skip:
            overflow = jnp.logical_or(overflow, extra_skip)
        if compute_norm and clip and clip > 0:
            # Same expression as runtime.utils.clip_coefficient (kept
            # textually identical so the paths cannot diverge).
            coeff = jnp.minimum(1.0, clip / (grad_norm + 1e-6))
            use_coeff = True
        elif has_pre_coeff:
            coeff = pre_coeff.astype(jnp.float32)
            use_coeff = True
        else:
            coeff = jnp.asarray(1.0, jnp.float32)
            use_coeff = False
        skip = jnp.where(overflow, 1.0, 0.0).astype(jnp.float32)
        # SMEM scalar row: [neg_lr, bc1, bc2, coeff, inv, skip, 0, 0].
        scalars = jnp.stack(
            [base[0], base[1], base[2], coeff, base[3], skip,
             jnp.float32(0.0), jnp.float32(0.0)])[None]
        static = dict(b1=b1, b2=b2, eps=eps, wd=weight_decay,
                      coupled=not adam_w_mode, use_inv=use_inv,
                      use_coeff=use_coeff, one_pass=one_pass,
                      cast_dtype=cast_dtype)

        def first_index(nloc: int, sharded: bool = True):
            """This device's first element in the stochastic-rounding
            counter; replicas of an unsharded leaf must round alike. (A
            leaf sharded on a non-leading dim counts per shard, so its
            noise stream — never its distribution — depends on dp.)"""
            if axis is None or not sharded:
                return jnp.int32(0)
            return lax.axis_index(axis).astype(jnp.int32) * jnp.int32(nloc)

        new_p, new_m, new_v, new_cast = [], [], [], []
        for k, (gi, dt, idxs, sizes, Lpad) in enumerate(groups):
            sr, cast = group_modes[k]
            seed = jnp.stack([seed0 + jnp.int32(gi),
                              first_index(int(gbufs[k].size))])[None]
            pf, mn, vn, cf = _run_group(
                _flat_1d(gbufs[k]), _flat_1d(pbufs[k]),
                _flat_1d(ms[k]), _flat_1d(vs[k]), scalars, seed,
                sr=sr, cast=cast, out_dtype=dt, **static)
            shape = gbufs[k].shape
            new_p.append(pf.reshape(shape))
            new_m.append(mn.reshape(shape))
            new_v.append(vn.reshape(shape))
            if cast:
                new_cast.append(cf.reshape(shape))
        leaf_p, leaf_m, leaf_v, leaf_cast = [], [], [], []
        with jax.named_scope("kernel"):
            for k, (dt, sr, cast, sharded) in enumerate(leaf_modes):
                # The counter hash mixes seed and element index by XOR:
                # a seed per leaf, itself hashed, keeps two leaves' noise
                # streams from being shifts of one another.
                seed = jnp.stack([
                    hash_u32((seed0 + jnp.int32(len(groups) + k))
                             .astype(jnp.uint32)).astype(jnp.int32),
                    first_index(int(lps[k].size), sharded)])[None]
                outs = _update_leaf(lgs[k], lps[k], lms[k], lvs[k], scalars,
                                    seed, sr=sr, cast=cast, out_dtype=dt,
                                    **static)
                leaf_p.append(outs[0])
                leaf_m.append(outs[1])
                leaf_v.append(outs[2])
                if cast:
                    leaf_cast.append(outs[3])
        return (tuple(new_p), tuple(new_m), tuple(new_v),
                tuple(new_cast), tuple(leaf_p), tuple(leaf_m),
                tuple(leaf_v), tuple(leaf_cast), grad_norm, overflow)

    def _inplace_specs(p_leaves, treedef, plan):
        """(PartitionSpec, sharded over dp?) of each in-place leaf under
        ``shard_map``: the engine's (``leaf_specs``) or the
        first-divisible-dim rule its gradient and moment shardings
        follow (zero/partition.py)."""
        from ..runtime.zero.partition import _leaf_spec, spec_dp_dim
        tree = leaf_specs() if leaf_specs is not None else None
        if tree is not None:
            flat = treedef.flatten_up_to(tree)
            specs = [flat[i] for i in plan.inplace]
        else:
            specs = [_leaf_spec(p_leaves[i].shape, dp, shard_axis)
                     for i in plan.inplace]
        return [(sp, spec_dp_dim(sp, shard_axis) is not None)
                for sp in specs]

    def _apply_impl(grads, state, params, *, pre_coeff=None,
                    inv_scale=None, clip=0.0, fp16=False,
                    compute_norm=False, extra_skip=None, one_pass=False,
                    sr_key=None, cast_dtype=None):
        if params is None:
            raise ValueError("fused_adam requires params")
        p_leaves, treedef = _leaves(params)
        g_leaves = treedef.flatten_up_to(grads)
        plan = update_plan(p_leaves)
        groups = _group_plan(p_leaves, plan)
        base = _base_scalars(state.count, inv_scale)
        seed0 = jax.random.bits(sr_key, (), jnp.uint32).astype(jnp.int32) \
            if sr_key is not None else jnp.zeros((), jnp.int32)
        constrain = _row_sharding() if use_shard_map else None
        gbufs, pbufs, ms, vs = [], [], [], []
        group_modes = []
        for gi, dt, idxs, sizes, Lpad in groups:
            # Grads flatten in f32, NOT the param dtype: master-free
            # engines hand in f32-accumulated grads over bf16 params,
            # and truncating them here would defeat the kernel's
            # f32-second-moment guarantee before it ever reads them.
            # (Narrow grads widen in this copy, exactly; the packed
            # leaves are too few to earn a second kernel geometry.)
            gbufs.append(_flatten_group(g_leaves, idxs, jnp.float32,
                                        shards, Lpad, constrain))
            pbufs.append(_flatten_group(p_leaves, idxs, dt, shards,
                                        Lpad, constrain))
            m2, v2 = state.m[gi], state.v[gi]
            if constrain is not None:
                m2 = lax.with_sharding_constraint(
                    m2.reshape(shards, Lpad), constrain)
                v2 = lax.with_sharding_constraint(
                    v2.reshape(shards, Lpad), constrain)
            ms.append(m2)
            vs.append(v2)
            group_modes.append(_modes(dt, sr_key is not None, cast_dtype))
        # In-place leaves enter as they are: the gradient at the width
        # it arrives (a widening pass here is opaque to the Pallas call
        # and would be materialized: 2 B read + 4 B written an element,
        # then 4 B read again), the parameter and both moments.
        lgs = tuple(g_leaves[i] for i in plan.inplace)
        lps = tuple(p_leaves[i] for i in plan.inplace)
        specs = _inplace_specs(p_leaves, treedef, plan) if use_shard_map \
            else [(None, False)] * len(plan.inplace)
        leaf_modes = tuple(
            (jnp.dtype(p.dtype),) +
            _modes(jnp.dtype(p.dtype), sr_key is not None, cast_dtype) +
            (sharded,) for p, (_, sharded) in zip(lps, specs))
        pre_coeff_arr = jnp.asarray(
            1.0 if pre_coeff is None else pre_coeff, jnp.float32)
        extra_skip_arr = jnp.asarray(
            False if extra_skip is None else extra_skip)
        region = functools.partial(
            _kernel_region, groups=groups, clip=clip, fp16=fp16,
            use_inv=inv_scale is not None, one_pass=one_pass,
            compute_norm=compute_norm,
            has_pre_coeff=pre_coeff is not None,
            use_extra_skip=extra_skip is not None,
            group_modes=tuple(group_modes), leaf_modes=leaf_modes,
            cast_dtype=cast_dtype, local=use_shard_map)
        if use_shard_map:
            from jax.sharding import PartitionSpec as P
            from ..parallel.comm import shard_map
            row = P(shard_axis, None)
            nbuf = len(groups)
            ncast = sum(1 for _, c in group_modes if c)
            lsp = tuple(sp for sp, _ in specs)
            lcast = tuple(sp for sp, md in zip(lsp, leaf_modes) if md[2])
            fn = shard_map(
                region, mesh=mesh,
                in_specs=(P(), P(), P(), P(),
                          (row,) * nbuf, (row,) * nbuf,
                          (row,) * nbuf, (row,) * nbuf,
                          lsp, lsp, lsp, lsp),
                out_specs=((row,) * nbuf, (row,) * nbuf, (row,) * nbuf,
                           (row,) * ncast, lsp, lsp, lsp, lcast,
                           P(), P()),
                # Manual over EVERY mesh axis (the others are size 1 —
                # the engine only takes this path on a pure-dp mesh): a
                # Mosaic kernel under a partly-auto shard_map is refused
                # ("cannot be automatically partitioned").
                axis_names=set(mesh.axis_names), check_vma=False)
        else:
            fn = region
        (new_pb, new_mb, new_vb, new_cb, leaf_p, leaf_m, leaf_v, leaf_c,
         grad_norm, overflow) = fn(
            base, seed0, pre_coeff_arr, extra_skip_arr, tuple(gbufs),
            tuple(pbufs), tuple(ms), tuple(vs), lgs, lps,
            tuple(state.leaf_m), tuple(state.leaf_v))

        new_leaves = list(p_leaves)
        cast_leaves = list(p_leaves) if cast_dtype is not None else None
        ci = 0
        for k, (gi, dt, idxs, sizes, Lpad) in enumerate(groups):
            for i, a in _unflatten_group(new_pb[k], p_leaves, idxs,
                                         shards).items():
                new_leaves[i] = a
            if cast_leaves is not None:
                if group_modes[k][1]:
                    src = new_cb[ci]
                    ci += 1
                    for i, a in _unflatten_group(src, p_leaves, idxs,
                                                 shards).items():
                        cast_leaves[i] = a
                else:
                    # Same dtype (or SR bf16 write): the param output IS
                    # the compute-dtype value — alias, don't copy.
                    for i in idxs:
                        cast_leaves[i] = new_leaves[i]
        ci = 0
        for k, i in enumerate(plan.inplace):
            new_leaves[i] = leaf_p[k]
            if cast_leaves is not None:
                if leaf_modes[k][2]:
                    cast_leaves[i] = leaf_c[ci]
                    ci += 1
                else:
                    cast_leaves[i] = leaf_p[k]
        if cast_leaves is not None:
            # Non-float leaves mirror _cast_floats: passed through as-is.
            cast_params = jax.tree_util.tree_unflatten(
                treedef, cast_leaves)
        else:
            cast_params = None
        new_params = jax.tree_util.tree_unflatten(treedef, new_leaves)
        if one_pass:
            count_inc = state.count + \
                jnp.where(overflow, 0, 1).astype(jnp.int32)
        else:
            count_inc = state.count + 1
        new_state = FusedAdamState(
            count=count_inc,
            m=tuple(b.reshape(-1) for b in new_mb),
            v=tuple(b.reshape(-1) for b in new_vb),
            leaf_m=leaf_m, leaf_v=leaf_v)
        return new_params, new_state, cast_params, grad_norm, overflow

    def _apply(grads, state, params, clip_coeff=None, sr_key=None):
        """PR-1 two-pass API: the caller resolved clip/overflow."""
        new_params, new_state, _, _, _ = _apply_impl(
            grads, state, params, pre_coeff=clip_coeff, sr_key=sr_key)
        return new_params, new_state

    def _step(grads, state, params, *, clip=0.0, inv_scale=None,
              fp16=False, compute_norm=True, extra_skip=None,
              sr_key=None, cast_dtype=None) -> FusedStepOut:
        """One-pass clipped update (module docstring): grads may still
        carry the fp16 loss scale (``inv_scale`` unscales in-kernel);
        norm/overflow/clip/skip/cast all ride the single HBM pass."""
        new_params, new_state, cast_params, grad_norm, overflow = \
            _apply_impl(grads, state, params, inv_scale=inv_scale,
                        clip=clip, fp16=fp16, compute_norm=compute_norm,
                        extra_skip=extra_skip, one_pass=True,
                        sr_key=sr_key, cast_dtype=cast_dtype)
        return FusedStepOut(new_params, new_state, cast_params,
                            grad_norm, overflow)

    def update_fn(updates, state, params=None):
        """optax-compatible wrapper: returns delta-style updates so generic
        callers (``optax.apply_updates``) keep working. The engine's train
        steps call ``fused_step``/``fused_apply`` instead for the true
        single-pass write."""
        new_params, new_state = _apply(updates, state, params)
        deltas = jax.tree_util.tree_map(
            lambda np_, p: (np_.astype(jnp.float32) -
                            p.astype(jnp.float32)).astype(np_.dtype)
            if hasattr(p, "dtype") and jnp.issubdtype(p.dtype, jnp.floating)
            else jnp.zeros_like(p) if hasattr(p, "dtype") else p,
            new_params, params)
        return deltas, new_state

    return FusedGradientTransformation(init=init_fn, update=update_fn,
                                       fused_apply=_apply,
                                       fused_step=_step)


class FusedGradientTransformation(NamedTuple):
    """optax.GradientTransformation duck-type + the fused entry points."""
    init: Callable[[Any], FusedAdamState]
    update: Callable[..., Tuple[Any, FusedAdamState]]
    fused_apply: Callable[..., Tuple[Any, FusedAdamState]]
    fused_step: Callable[..., FusedStepOut]
