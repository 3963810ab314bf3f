"""1F1B SPMD pipeline — O(P) activation memory, fwd/bwd interleaved.

The GPipe-profile pipeline (spmd.py) banks O(M) boundary tensors: the
embedded input bank, the last-stage output bank, and — because reverse-mode
autodiff runs ALL forward ticks before ANY backward tick — one saved stage
input per tick. The reference's TrainSchedule instead interleaves: each
stage starts micro i's backward as soon as its forward chain allows, so at
most O(P) activations are ever live (reference runtime/pipe/schedule.py:
182-290, the 1F1B ordering).

Reverse-mode autodiff CANNOT express that interleaving (it is two-phase by
construction), so this module differentiates MANUALLY: one primal
``lax.scan`` over M + 2(P-1) ticks computes loss AND gradients directly.
Each tick every stage runs — uniformly, so no conditional collectives —

  forward sub-tick:  embed (masked to stage 0) → stage_fn → save input in
                     a 2P-slot ring; last stage feeds the tick's output
                     straight into the head's value_and_grad (micro i's
                     backward starts the same tick its forward ends);
  backward sub-tick: re-run the stage under ``jax.vjp`` at the ring-saved
                     input (same per-micro rng), pull the incoming
                     cotangent through, accumulate block grads locally
                     (they stay pipe-sharded — exactly the param layout)
                     and tied/shared grads via an end-of-scan psum (the
                     reference's ReduceTiedGrads, pipe/engine.py:208-227);
  rotate:            activations ppermute up, cotangents ppermute down.

Schedule (micro index as a function of tick t on stage r):
  forward  f = t - r              (stage 0 leads)
  head     h = t - (P-1)          (last stage, same tick as its fwd)
  backward b = t - 2(P-1) + r     (cotangent wavefront back down)
Ring lifetime of a saved input on stage r is 2(P-1-r) ticks, so a ring of
R = 2P slots indexed by micro mod R never collides: O(P), independent of M.

Compute parity with the remat GPipe path: both run fwd twice + bwd once
per layer (here the re-run is inside ``jax.vjp``). Each sub-tick (embed
fwd, stage fwd, head, stage bwd, embed bwd) is ``lax.cond``-gated on a
predicate that is a function of the TICK INDEX ONLY — uniform across
devices — so warmup/drain ticks skip the work they cannot use. Uniformity
is load-bearing: a per-RANK predicate (e.g. ``r == last`` for the head)
puts the partitioner-inserted dp/mp collectives of the branch body on
some devices' execution paths and not others', and the program deadlocks
at the next collective rendezvous (observed on the 8-device dryrun:
ranks waiting on different op_ids of the same scan). Per-rank validity is
therefore applied INSIDE the branch as ``jnp.where`` selects — a select
DISCARDS the masked side, so a warmup/drain tick's inf/NaN (plausible
under fp16: the head/vjp sees stale buffers) cannot poison the
accumulators the way multiplicative ``0*g`` masking could.

Wall-clock: in a lockstep pipeline the off-stage work that remains (the
head on non-last ranks during the M central ticks) runs in PARALLEL with
the real head on the last rank — it wastes chip-FLOPs, not tick latency.
The reclaimable latency is the warmup/drain sub-ticks, which the uniform
gates remove. ``gate_offstage=False``
recovers the ungated run-everything-and-select variant.

fp16 loss scaling: the engine passes its (traced) loss scale; the head
loss is multiplied by it inside the tick, so every cotangent flowing down
the pipe — and every accumulated gradient — is scaled exactly as the
autodiff path's scaled-loss trick produces, and the engine's existing
unscale + overflow-vote machinery applies unchanged. The RETURNED loss is
unscaled (scale is a power of two; the division is exact).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ...parallel import comm
from ...parallel.topology import PP_AXIS
from .spmd import _split_batch, _to_micro


def tick_table(num_micro: int, num_stages: int):
    """The scan's schedule AS DATA: ``table[t][r]`` lists the work items
    tick ``t``'s gates admit on stage ``r`` — ``("F", m)`` stage forward,
    ``("H", m)`` head loss + its grad (last stage, same tick as its
    forward), ``("B", m)`` stage backward. Exactly the clock the scan body
    runs (``f = t - r``, ``h = t - (P-1)``, ``b = t - 2(P-1) + r``; module
    docstring), exported so ``runtime/pipe/schedule.py``'s TrainSchedule —
    the reference's instruction-list specification — can be asserted
    against it as the 1F1B oracle (tests/test_pipe_1f1b.py)."""
    M, Pstages = num_micro, num_stages
    last = Pstages - 1
    table = []
    for t in range(M + 2 * last):
        per_stage = []
        for r in range(Pstages):
            evs = []
            f = t - r
            if 0 <= f < M:
                evs.append(("F", f))
            h = t - last
            if r == last and 0 <= h < M:
                evs.append(("H", h))
            b = t - 2 * last + r
            if 0 <= b < M:
                evs.append(("B", b))
            per_stage.append(evs)
        table.append(per_stage)
    return table


def spmd_pipeline_1f1b_grads(embed_fn: Callable, stage_fn: Callable,
                             head_fn: Callable, num_stages: int,
                             num_micro_batches: int, mesh: Mesh,
                             gate_offstage: bool = True) -> Callable:
    """Build ``grads_fn(params, batch, rng, scale=None) ->
    (unscaled_mean_loss, scale-multiplied grads)``.

    Params pytree: ``{"shared": replicated-over-pipe, "blocks": stacked,
    sharded over pipe}`` — same contract as spmd_pipeline_loss; grads come
    back in the same structure/sharding as params. ``scale`` is the fp16
    loss scale (defaults to 1.0, where grads are plain gradients).

    ``gate_offstage``: cond-skip warmup/drain sub-ticks via tick-uniform
    gates (default). False runs every sub-tick everywhere and
    select-masks — only for measuring the gating win.
    """
    M, Pstages = num_micro_batches, num_stages
    T = M + 2 * (Pstages - 1)
    R = 2 * Pstages                      # ring slots (>= max lifetime + 1)

    def per_stage(blocks_local, shared, micro_tokens, micro_targets, rng,
                  scale, cdtype, xshape):
        """Runs on every pipe rank; returns (loss_sum, dblocks, dshared)."""
        r = lax.axis_index(PP_AXIS)
        last = Pstages - 1

        def mkey(i):
            # Per-MICRO key (not per-tick): the backward sub-tick re-runs
            # the stage under vjp and must regenerate identical dropout.
            return jax.random.fold_in(jax.random.fold_in(rng, i), r)

        def head_loss(sh, y, tgt, key):
            # mean-over-micros normalization AND the fp16 loss scale are
            # folded into the cotangent here — everything downstream
            # (dy, dx, dblocks, dshared) comes out scaled, exactly like
            # the autodiff path's scaled-loss trick.
            return head_fn(sh, y, tgt, key).astype(jnp.float32) * scale / M

        def ugate(pred, true_thunk, false_thunk):
            # ``pred`` MUST be tick-uniform (a function of t, never of the
            # rank): all devices take the same branch, so the collective
            # sequence cannot diverge. Per-rank validity goes INSIDE the
            # branch as selects.
            if gate_offstage:
                return lax.cond(pred, true_thunk, false_thunk)
            out, zero = true_thunk(), false_thunk()
            return jax.tree_util.tree_map(
                lambda a, z: jnp.where(pred, a, z), out, zero)

        zeros_x = jnp.zeros(xshape, cdtype)
        zeros_shared = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, p.dtype), shared)
        zeros_blocks = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, p.dtype), blocks_local)
        carry0 = (
            zeros_x,                                  # fwd_buf
            zeros_x,                                  # bwd_buf (cotangent)
            # R live slots + 1 trash slot for warmup/drain ticks whose
            # clipped micro index must not clobber a live save.
            jnp.zeros((R + 1,) + xshape, cdtype),     # saved-input ring
            jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), blocks_local),
            jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), shared),
            jnp.zeros((), jnp.float32),               # loss sum
        )

        def tick(carry, t):
            fwd_buf, bwd_buf, ring, g_blocks, g_shared, loss_acc = carry

            # Tick-uniform gate windows (functions of t only; see module
            # docstring for why they must not depend on the rank):
            #   embed fwd   stage 0's f = t            → t < M
            #   stage fwd   some rank has 0 ≤ t-r < M  → t < M + last
            #   head        last rank's h = t - last   → last ≤ t < M+last
            #   stage bwd   some rank has valid b      → t ≥ last
            #   embed bwd   rank 0's b = t - 2·last    → t ≥ 2·last
            emb_t = t < M
            fwd_t = t < M + last
            head_t = jnp.logical_and(t >= last, t < M + last)
            bwd_t = t >= last
            embbwd_t = t >= 2 * last

            # ---------------- forward sub-tick ----------------
            f = t - r
            fc = jnp.clip(f, 0, M - 1)
            f_ok = jnp.logical_and(f >= 0, f < M)
            key_f = mkey(fc)
            tok_f = lax.dynamic_index_in_dim(micro_tokens, fc, 0,
                                             keepdims=False)
            x0 = ugate(
                emb_t,
                lambda: embed_fn(shared, tok_f, key_f).astype(cdtype),
                lambda: zeros_x)
            x_in = jnp.where(r == 0, x0, fwd_buf)
            y = ugate(
                fwd_t,
                lambda: stage_fn(blocks_local, x_in, key_f).astype(cdtype),
                lambda: zeros_x)
            ring = lax.dynamic_update_index_in_dim(
                ring, x_in, jnp.where(f_ok, fc % R, R), 0)

            # Head + its grad on the tick's own output (last stage: micro
            # h == f). The gate skips the whole vocab projection + vjp on
            # the 2·last warmup/drain ticks; within the window, off-stage
            # ranks still run it in parallel (latency-free) and the
            # selects below discard their garbage.
            h = t - last
            hc = jnp.clip(h, 0, M - 1)
            tgt_h = lax.dynamic_index_in_dim(micro_targets, hc, 0,
                                             keepdims=False)
            key_h = jax.random.fold_in(rng, M + hc)
            valid_h = jnp.logical_and(jnp.logical_and(h >= 0, h < M),
                                      r == last)

            def run_head():
                l, (gsh, gy) = jax.value_and_grad(
                    head_loss, argnums=(0, 1))(shared, y, tgt_h, key_h)
                return (jnp.where(valid_h, l, 0.0),
                        jax.tree_util.tree_map(
                            lambda g: jnp.where(valid_h, g,
                                                jnp.zeros_like(g)), gsh),
                        jnp.where(valid_h, gy.astype(cdtype), zeros_x))

            loss_h, dsh_head, dy = ugate(
                head_t, run_head,
                lambda: (jnp.zeros((), jnp.float32), zeros_shared, zeros_x))
            loss_acc = loss_acc + loss_h
            g_shared = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32), g_shared, dsh_head)

            # ---------------- backward sub-tick ----------------
            b = t - 2 * last + r
            bc = jnp.clip(b, 0, M - 1)
            b_ok = jnp.logical_and(b >= 0, b < M)
            key_b = mkey(bc)
            x_saved = lax.dynamic_index_in_dim(ring, bc % R, 0,
                                               keepdims=False)
            g_in = jnp.where(r == last, dy, bwd_buf)

            def run_bwd():
                _, vjp = jax.vjp(
                    lambda bl, xi: stage_fn(bl, xi, key_b), blocks_local,
                    x_saved)
                dblocks, dx = vjp(g_in)
                return (jax.tree_util.tree_map(
                            lambda g: jnp.where(b_ok, g,
                                                jnp.zeros_like(g)), dblocks),
                        dx.astype(cdtype))

            dblocks, dx = ugate(
                bwd_t, run_bwd, lambda: (zeros_blocks, zeros_x))
            g_blocks = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32), g_blocks, dblocks)

            # Embedding backward (tied front): stage 0 pulls its input
            # cotangent into the shared params.
            tok_b = lax.dynamic_index_in_dim(micro_tokens, bc, 0,
                                             keepdims=False)
            valid_e = jnp.logical_and(b_ok, r == 0)

            def run_embed_bwd():
                _, evjp = jax.vjp(
                    lambda sh: embed_fn(sh, tok_b, key_b).astype(cdtype),
                    shared)
                (dsh_emb,) = evjp(dx)
                return jax.tree_util.tree_map(
                    lambda g: jnp.where(valid_e, g, jnp.zeros_like(g)),
                    dsh_emb)

            dsh_emb = ugate(embbwd_t, run_embed_bwd, lambda: zeros_shared)
            g_shared = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32), g_shared, dsh_emb)

            # ---------------- rotate (bf16 boundaries, as in spmd.py) ----
            fwd_next = lax.ppermute(
                y, PP_AXIS, [(i, i + 1) for i in range(Pstages - 1)])
            bwd_next = lax.ppermute(
                dx, PP_AXIS, [(i + 1, i) for i in range(Pstages - 1)])
            return (fwd_next, bwd_next, ring, g_blocks, g_shared,
                    loss_acc), None

        (_, _, _, g_blocks, g_shared, loss_sum), _ = lax.scan(
            tick, carry0, jnp.arange(T))
        # Shared/tied grads are partial per stage (embed on 0, head on
        # P-1); the psum is the ReduceTiedGrads collective. Loss lives on
        # the last stage only, so the psum just broadcasts it.
        g_shared = jax.tree_util.tree_map(
            lambda g: lax.psum(g, PP_AXIS), g_shared)
        loss_sum = lax.psum(loss_sum, PP_AXIS)
        return loss_sum, g_blocks, g_shared

    def grads_fn(params, batch, rng, scale=None):
        scale = jnp.asarray(1.0, jnp.float32) if scale is None else scale
        tokens, targets = _split_batch(batch)
        micro_tokens = _to_micro(tokens, M)       # [M, mb, S]
        micro_targets = _to_micro(targets, M)
        shared = params["shared"]

        # Embedded-activation shape (per micro-batch), via eval_shape so no
        # FLOPs run outside the pipeline.
        x_shape = jax.eval_shape(
            lambda sh, tk: embed_fn(sh, tk, jax.random.PRNGKey(0)),
            shared, jax.tree_util.tree_map(lambda a: a[0], micro_tokens))
        cdtype = x_shape.dtype

        mapped = comm.shard_map(
            partial(per_stage, cdtype=cdtype, xshape=x_shape.shape),
            mesh=mesh,
            in_specs=(P(PP_AXIS), P(), P(), P(), P(), P()),
            out_specs=(P(), P(PP_AXIS), P()),
            axis_names={PP_AXIS},
            check_vma=False)
        loss, g_blocks, g_shared = mapped(
            params["blocks"], shared, micro_tokens, micro_targets, rng,
            scale)
        # Grads stay SCALED (the engine unscales + overflow-votes, same as
        # its autodiff path); the reported loss is unscaled — scale is a
        # power of two, so the division is exact.
        return loss / scale, {"shared": g_shared, "blocks": g_blocks}

    return grads_fn
