"""ZeRO partitioning as sharding specs.

The reference implements optimizer-state partitioning (stage1.py:348-458) and
gradient partitioning (stage2.py:583-738) with manual flatten/bucket/
reduce-to-owner machinery. On TPU the same placement is *declared*: each
optimizer-state leaf gets a NamedSharding that splits it across the dp mesh
axis, and XLA's SPMD partitioner compiles the training step into
reduce-scatter(grads) → sharded update → all-gather(params) — the exact
communication schedule of ZeRO-2 (cf. SURVEY §2.9), chosen automatically and
overlapped by the latency-hiding scheduler instead of hand-managed CUDA
streams.

Stage 3 extends the same declaration to the PARAMETER tree itself
(``stage3_param_specs``): params are born dp-sharded on the same
first-divisible-dim rule grads and moments follow (element alignment — the
optimizer apply stays shard-local), gathered just-in-time for use, and
re-sharded after (runtime/zero/stage3.py holds the gather machinery).
"""
from __future__ import annotations

from typing import Any, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _leaf_spec(shape, axis_size: int, axis_name: str) -> P:
    """Shard the first dimension divisible by the axis; else replicate.

    The reference pads flattened groups to make them divisible
    (stage1.py:32-78); we instead keep natural array shapes and replicate the
    (rare, small) leaves that don't divide — same memory story for the bulky
    moment tensors, no repacking.
    """
    for i, d in enumerate(shape):
        if d >= axis_size and d % axis_size == 0:
            return P(*([None] * i + [axis_name]))
    return P()


def _layer_dp(base: P, shape, axis_size: int, axis_name: str) -> P:
    """Add the dp axis onto the first unsharded divisible dim of ``base``."""
    parts = list(base) + [None] * (len(shape) - len(base))
    for i, d in enumerate(shape):
        if parts[i] is None and d >= axis_size and d % axis_size == 0:
            parts[i] = axis_name
            break
    return P(*parts)


_NO_BASE = object()     # sentinel: leaf is NOT param-structured


def base_spec_leaves(opt_state: Any, params: Any, param_specs: Any,
                     default: Any = P()):
    """Per-leaf base (TP) PartitionSpecs for an optimizer-state pytree.

    Optimizer moments mirror the param tree *structurally* (optax states
    nest copies of the param pytree), so subtrees whose treedef equals the
    param treedef inherit ``param_specs`` wholesale; all other leaves
    (step counters etc.) get ``default`` (replicated by default;
    stage3_state_shardings passes the ``_NO_BASE`` sentinel to tell
    "not param-structured" apart from "replicated param"). Structural
    matching avoids the shape-collision trap of keying by array shape
    (two same-shaped params with different specs).
    """
    p_def = jax.tree_util.tree_structure(params)

    def params_like(node) -> bool:
        try:
            return jax.tree_util.tree_structure(node) == p_def
        except Exception:
            return False

    base_tree = jax.tree_util.tree_map(
        lambda node: param_specs if params_like(node) else default,
        opt_state, is_leaf=params_like)
    # Flatten with P treated as a leaf (P is a tuple subclass, so a plain
    # flatten would descend into it).
    return jax.tree_util.tree_leaves(
        base_tree, is_leaf=lambda x: isinstance(x, P) or x is _NO_BASE)


def _leaf_sharding(leaf, base: Optional[P], mesh: Mesh, axis_size: int,
                   axis_name: Optional[str]) -> NamedSharding:
    """The single per-leaf dispatch shared by grads and optimizer moments —
    one implementation so their layouts stay element-aligned by
    construction (no resharding inside the optimizer math)."""
    if not hasattr(leaf, "shape") or getattr(leaf, "ndim", 0) < 1:
        return NamedSharding(mesh, P())
    if base is not None:
        spec = _layer_dp(base, leaf.shape, axis_size, axis_name) \
            if axis_name else base
        return NamedSharding(mesh, spec)
    if axis_name:
        return NamedSharding(
            mesh, _leaf_spec(leaf.shape, axis_size, axis_name))
    return NamedSharding(mesh, P())


def zero_shardings(opt_state: Any, mesh: Mesh, axis_name: Optional[str],
                   params: Any = None, param_specs: Any = None) -> Any:
    """NamedShardings for an optax state pytree.

    ``axis_name`` (usually the dp axis) is layered onto each leaf's first
    still-unsharded divisible dimension — ZeRO partitioning. With tensor
    parallelism, pass ``params`` + ``param_specs``: moments keep the TP
    sharding and dp is layered on top — the reference's ZeRO-under-Megatron
    configuration (stage2.py:162-167). ``axis_name=None`` applies only the
    TP layout (no ZeRO).
    """
    axis_size = mesh.shape[axis_name] if axis_name else 1
    leaves, treedef = jax.tree_util.tree_flatten(opt_state)

    if params is not None and param_specs is not None:
        bases = base_spec_leaves(opt_state, params, param_specs)
    else:
        bases = [None] * len(leaves)

    out = [_leaf_sharding(leaf, base, mesh, axis_size, axis_name)
           for leaf, base in zip(leaves, bases)]
    return jax.tree_util.tree_unflatten(treedef, out)


def grad_shardings(params: Any, mesh: Mesh, axis_name: str,
                   param_specs: Any = None) -> Any:
    """ZeRO-2: NamedShardings for the gradient-accumulation buffer.

    The reference's stage 2 never materializes an unpartitioned gradient:
    per-param hooks copy grads into an IPG bucket and reduce each slice to
    its owner rank (stage2.py:613-738). The TPU equivalent is declarative —
    constrain the accumulated grads to be dp-sharded, and XLA compiles the
    cross-dp gradient reduction as reduce-scatter with each chip holding
    1/dp of every gradient, which the sharded optimizer update consumes
    in place before the updated params all-gather.

    With TP (``param_specs``), dp is layered onto each leaf's first free
    divisible dim, mirroring ``zero_shardings`` for the moments so grads,
    moments, and updates are element-aligned (no resharding inside the
    optimizer math).
    """
    axis_size = mesh.shape[axis_name]
    if param_specs is None:
        return jax.tree_util.tree_map(
            lambda p: _leaf_sharding(p, None, mesh, axis_size, axis_name),
            params)
    # tree_map uses params' structure; the matching param_specs subtree at
    # each param leaf is the P itself (flatten_up_to stops at leaves).
    return jax.tree_util.tree_map(
        lambda p, base: _leaf_sharding(p, base, mesh, axis_size, axis_name),
        params, param_specs)


def stage3_param_specs(params: Any, axis_size: int, axis_name: str,
                       param_specs: Any = None,
                       scan_paths: Optional[Any] = None) -> Any:
    """ZeRO-3: per-leaf ``PartitionSpec``s for the PARAMETER tree itself.

    The rule is ``_leaf_spec`` — the same first-divisible-dim rule grads
    (``grad_shardings``) and moments (``zero_shardings``) follow, so
    params, grads and optimizer state stay element-aligned and the
    shard-local optimizer apply needs no resharding.

    ``scan_paths``: predicate ``(path_str) -> bool`` marking leaves the
    model gathers ITSELF per layer inside its stacked-layer scan
    (runtime/zero/stage3.py). For those leaves dim 0 is the layer axis —
    sharding it would turn the per-layer gather into a one-owner
    broadcast and break the scan's layer slicing — so the dp axis goes on
    the first divisible dim >= 1 instead (replicated when none divides).

    With tensor parallelism pass ``param_specs`` (the TP base): dp is
    layered onto each leaf's first free divisible dim, mirroring
    ``grad_shardings``.
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    bases = None
    if param_specs is not None:
        bases = treedef.flatten_up_to(param_specs)

    def spec_for(i: int, path, leaf) -> P:
        shape = getattr(leaf, "shape", None)
        if shape is None or getattr(leaf, "ndim", 0) < 1:
            return P() if bases is None else bases[i]
        scanned = scan_paths is not None and \
            scan_paths(jax.tree_util.keystr(path))
        base = bases[i] if bases is not None else P()
        parts = list(base) + [None] * (len(shape) - len(base))
        start = 1 if scanned else 0
        for d in range(start, len(shape)):
            if parts[d] is None and shape[d] >= axis_size \
                    and shape[d] % axis_size == 0:
                parts[d] = axis_name
                break
        # No divisible dim (scanned leaves additionally skip the layer
        # axis): stays replicated over dp — correct, just unpartitioned.
        return P(*parts)

    specs = [spec_for(i, path, leaf) for i, (path, leaf) in enumerate(flat)]
    return jax.tree_util.tree_unflatten(treedef, specs)


def stage3_state_shardings(opt_state: Any, mesh: Mesh, axis_name: str,
                           params: Any, stage3_specs: Any) -> Any:
    """Stage-3 optimizer-state shardings: moments MIRROR the stage-3
    param layout wherever the state is param-structured (so the
    shard-local update needs no resharding between grad, param and
    moment), and non-param-structured leaves (the fused optimizer's flat
    moment buffers) fall back to the plain ``_leaf_spec`` dp rule —
    their V-interleaved rows stay dp-sharded exactly as under stage
    1/2. The fused optimizer's per-leaf moments mirror their leaf."""
    axis_size = int(mesh.shape[axis_name])
    bases = base_spec_leaves(opt_state, params, stage3_specs,
                             default=_NO_BASE)
    leaves, treedef = jax.tree_util.tree_flatten(opt_state)
    out = []
    for leaf, base in zip(leaves, bases):
        if not hasattr(leaf, "shape") or getattr(leaf, "ndim", 0) < 1:
            out.append(NamedSharding(mesh, P()))
        elif base is not _NO_BASE:
            out.append(NamedSharding(mesh, base))
        else:
            out.append(NamedSharding(
                mesh, _leaf_spec(leaf.shape, axis_size, axis_name)))
    shardings = jax.tree_util.tree_unflatten(treedef, out)
    if getattr(opt_state, "leaf_m", None):
        # The fused optimizer's in-place leaves: moments of the leaf's
        # own shape, so they take the leaf's own stage-3 spec (a scanned
        # leaf's is NOT the plain rule's).
        from ...ops.fused_update import update_plan
        p_leaves, p_def = jax.tree_util.tree_flatten(params)
        specs = p_def.flatten_up_to(stage3_specs)
        own = tuple(NamedSharding(mesh, specs[i])
                    for i in update_plan(p_leaves).inplace)
        shardings = shardings._replace(leaf_m=own, leaf_v=own)
    return shardings


def spec_dp_dim(spec: P, axis_name: str) -> Optional[int]:
    """Index of the dimension ``spec`` partitions over ``axis_name``
    (None when unsharded on that axis)."""
    for i, entry in enumerate(spec):
        if entry == axis_name or (isinstance(entry, (tuple, list)) and
                                  axis_name in entry):
            return i
    return None


def describe_sharding(opt_state: Any, shardings: Any) -> str:
    """Human-readable partition report (parity with stage1's logging)."""
    lines = []
    leaves, _ = jax.tree_util.tree_flatten(opt_state)
    shard_leaves, _ = jax.tree_util.tree_flatten(shardings)
    sharded = replicated = 0
    for leaf, sh in zip(leaves, shard_leaves):
        if hasattr(leaf, "shape") and any(s is not None for s in sh.spec):
            sharded += getattr(leaf, "size", 0)
        else:
            replicated += getattr(leaf, "size", 0)
    total = max(1, sharded + replicated)
    lines.append(f"ZeRO sharding: {sharded/total:.1%} of optimizer-state "
                 f"elements partitioned, {replicated/total:.1%} replicated")
    return "\n".join(lines)
