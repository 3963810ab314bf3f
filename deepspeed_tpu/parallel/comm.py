"""Distributed bootstrap and thin collective API.

Parity with reference ``utils/distributed.py`` (init_distributed w/ NCCL
default + MPI env discovery) and ``runtime/pipe/p2p.py`` (2-rank broadcast
p2p). TPU-native mapping:

- bootstrap = ``jax.distributed.initialize(coordinator, num_processes,
  process_id)`` driven by env vars the launcher sets;
- collectives = XLA ops over *named mesh axes* usable under ``shard_map``:
  ``all_reduce (psum)``, ``reduce_scatter (psum_scatter)``, ``all_gather``,
  ``broadcast``, ``permute (ppermute)``. The reference's
  p2p-as-2-rank-broadcast trick becomes ``ppermute``, which rides ICI
  directly and is strictly better.

Upper layers (engine, ZeRO, pipeline) only use this module, keeping them
backend-agnostic the way the reference's layers only use torch.distributed.
"""
from __future__ import annotations

import os
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..utils.logging import logger

_INITIALIZED = False


def shard_map(f, mesh=None, in_specs=None, out_specs=None, check_vma=None,
              axis_names=None):
    """``jax.shard_map`` with ``None`` meaning "the default" for the two
    optional knobs (``check_vma``; ``axis_names`` = the manual axes).
    Every in-repo caller routes through here."""
    kw = {}
    if check_vma is not None:
        kw["check_vma"] = check_vma
    if axis_names is not None:
        kw["axis_names"] = axis_names
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


def axis_in_scope(axis_name: str) -> bool:
    """True when ``axis_name`` is bound as a MANUAL axis at the current
    trace point (i.e. we are inside a shard_map over it, so
    ``lax.psum(axis_name)`` / ``lax.all_to_all(axis_name)`` are legal
    directly). Layers that normally wrap themselves in their own
    shard_map (the MoE FFN) use this to detect they are ALREADY inside
    one — the engine's factored explicit-gradient path runs the whole
    loss under a fully-manual shard_map over (expert, data) — and run
    their collectives bare instead of nesting."""
    return axis_name in jax.core.unsafe_get_axis_names_DO_NOT_USE()


def pvary(x, axis_name):
    """Mark ``x`` as varying over manual mesh axis/axes ``axis_name``.
    Only the axes ``x`` does not already vary over are cast: the vma
    checker rejects a cast of an already-varying value.  Under a
    ``check_vma=False`` shard_map nothing is tracked (even
    ``axis_index`` reads as invariant) and the cast is skipped — there
    its transpose would be a psum the untracked cotangent cannot pass."""
    names = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    if names[0] not in jax.typeof(lax.axis_index(names[0])).vma:
        return x

    def mark(a):
        missing = tuple(n for n in names if n not in jax.typeof(a).vma)
        return lax.pcast(a, missing, to="varying") if missing else a

    return jax.tree_util.tree_map(mark, x)


def init_distributed(dist_backend: str = "xla", distributed_port: int = 29500,
                     verbose: bool = True, init_method: Optional[str] = None) -> None:
    """Bring up the multi-host JAX runtime if env says we're multi-process.

    Env contract (set by deepspeed_tpu.launcher, mirrors the reference's
    MASTER_ADDR/RANK/WORLD_SIZE contract at launch.py:103-118):
    ``DS_COORDINATOR_ADDRESS``, ``DS_NUM_PROCESSES``, ``DS_PROCESS_ID``.
    Falls back to JAX's own cluster auto-detection; single-process otherwise.
    """
    global _INITIALIZED
    if _INITIALIZED:
        return
    coord = init_method or os.environ.get("DS_COORDINATOR_ADDRESS")
    nprocs = os.environ.get("DS_NUM_PROCESSES")
    pid = os.environ.get("DS_PROCESS_ID")
    if coord and nprocs and int(nprocs) > 1:
        if verbose:
            logger.info(f"Initializing JAX distributed: coordinator={coord} "
                        f"num_processes={nprocs} process_id={pid}")
        jax.distributed.initialize(coordinator_address=coord,
                                   num_processes=int(nprocs),
                                   process_id=int(pid) if pid is not None else None)
    _INITIALIZED = True


def is_initialized() -> bool:
    return _INITIALIZED


def get_world_size() -> int:
    return jax.device_count()

def get_local_device_count() -> int:
    return jax.local_device_count()

def get_process_index() -> int:
    return jax.process_index()

def get_process_count() -> int:
    return jax.process_count()


# --------------------------------------------------------------------- #
# Collectives over named mesh axes — call ONLY inside shard_map/pmap.
# --------------------------------------------------------------------- #
def all_reduce(x: Any, axis_name: str, op: str = "sum") -> Any:
    if op == "sum":
        return lax.psum(x, axis_name)
    if op == "mean":
        return lax.pmean(x, axis_name)
    if op == "max":
        return lax.pmax(x, axis_name)
    if op == "min":
        return lax.pmin(x, axis_name)
    raise ValueError(f"Unsupported all_reduce op {op}")


def reduce_scatter(x: Any, axis_name: str, scatter_dimension: int = 0,
                   tiled: bool = True) -> Any:
    """Sum-reduce then scatter shards along `scatter_dimension`."""
    return lax.psum_scatter(x, axis_name, scatter_dimension=scatter_dimension,
                            tiled=tiled)


def all_gather(x: Any, axis_name: str, axis: int = 0, tiled: bool = True) -> Any:
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def all_to_all(x: Any, axis_name: str, split_axis: int, concat_axis: int,
               tiled: bool = True) -> Any:
    """Exchange: split ``split_axis`` across the axis group, concatenate
    the received pieces on ``concat_axis``. Tiled (the default) keeps the
    rank; untiled requires ``split_axis`` to equal the axis size and
    unstacks it. Applied twice with ``split_axis == concat_axis`` it is
    an involution — the identity the MoE combine path relies on
    (deepspeed_tpu/moe/layer.py).

    The operand is marked varying over the axis first (``pvary``): the
    vma analysis requires an all-to-all input to be per-member-varying,
    and a replicated-marked operand would be rejected."""
    return lax.all_to_all(pvary(x, axis_name), axis_name,
                          split_axis=split_axis, concat_axis=concat_axis,
                          tiled=tiled)


def broadcast(x: Any, axis_name: str, src: int = 0) -> Any:
    """Every member receives src's value (reference p2p/broadcast parity)."""
    idx = lax.axis_index(axis_name)
    masked = jnp.where(idx == src, x, jnp.zeros_like(x))
    return lax.psum(masked, axis_name)


def permute(x: Any, axis_name: str, perm: Sequence[Tuple[int, int]]) -> Any:
    """Point-to-point pattern as a collective-permute.

    The reference implements stage p2p as dist.broadcast on 2-rank groups
    (p2p.py:31-55); ppermute expresses the same dataflow natively on ICI.
    """
    return lax.ppermute(x, axis_name, perm=list(perm))


def send_to_next(x: Any, axis_name: str, axis_size: int) -> Any:
    """Rotate +1 along the axis ring (pipeline activations)."""
    return permute(x, axis_name, [(i, (i + 1) % axis_size) for i in range(axis_size)])


def send_to_prev(x: Any, axis_name: str, axis_size: int) -> Any:
    """Rotate -1 along the axis ring (pipeline gradients)."""
    return permute(x, axis_name, [(i, (i - 1) % axis_size) for i in range(axis_size)])


def axis_index(axis_name: str) -> jax.Array:
    return lax.axis_index(axis_name)


def sparse_all_reduce(dense_grads_by_rank):
    """Host-side sparse (CSR) allreduce of row-sparse gradients.

    Parity with the engine's CSR embedding-gradient allreduce (reference
    engine.py:1197-1253: sparse grads are shipped as values+indices and
    re-densified after the gather). Inside jit, XLA reduces dense tensors
    over ICI and there is nothing to save; this host path is for
    DCN-bounded exchanges (multi-slice sync, elastic state shipping) where
    the wire volume is ``nnz_rows/vocab`` of the dense tensor.

    ``dense_grads_by_rank``: list of [rows, cols] arrays (one per rank).
    Returns (dense_sum, sparse_elements_shipped, dense_elements).
    """
    from ..runtime.csr_tensor import CSRTensor, all_gather_csr
    shards = [CSRTensor.from_dense(g) for g in dense_grads_by_rank]
    total = all_gather_csr(shards)
    shipped = sum(s.sparse_size() for s in shards)
    return total.to_dense(), shipped, total.dense_size


def csr_exchange_hosts(csr):
    """Cross-process CSR allgather: size gather → pad every shard to the
    max row count → allgather indices+values → trim → coalesce. Mirrors the
    reference's ``csr_all_gather`` padding protocol (engine.py:1234-1253)
    over the jax.distributed host channel; this is the DCN wire format
    whose volume is what sparse gradients exist to save.
    """
    import numpy as np
    from jax.experimental import multihost_utils
    from ..runtime.csr_tensor import CSRTensor, all_gather_csr
    n = np.asarray([csr.row_indices.shape[0]], np.int32)
    sizes = np.asarray(multihost_utils.process_allgather(n)).reshape(-1)
    mx = max(1, int(sizes.max()))
    pad = mx - int(n[0])
    idx = np.pad(csr.row_indices, (0, pad))
    vals = np.pad(np.asarray(csr.values, np.float32), ((0, pad), (0, 0)))
    all_idx = np.asarray(multihost_utils.process_allgather(idx))
    all_vals = np.asarray(multihost_utils.process_allgather(vals))
    shards = [CSRTensor(all_idx[p][:sizes[p]], all_vals[p][:sizes[p]],
                        csr.dense_shape)
              for p in range(sizes.shape[0]) if sizes[p] > 0]
    if not shards:
        return csr
    return all_gather_csr(shards)


def host_allreduce_sum(x: float) -> float:
    """Sum a host-side scalar across processes over the jax.distributed
    channel (the cross-rank reduction the partitioned offload grad norm
    needs, reference stage2.py:1371-1411)."""
    import numpy as np
    if jax.process_count() == 1:
        return float(x)
    from jax.experimental import multihost_utils
    gathered = multihost_utils.process_allgather(
        np.asarray([x], np.float32))
    return float(np.sum(np.asarray(gathered, np.float64)))
