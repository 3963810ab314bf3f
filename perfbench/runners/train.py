"""kind: train — optimizer steps through the user API
(``deepspeed_tpu.initialize`` / ``engine.train_batch``), cut from
``chip_smoke.phase_train``.

Set-up (outside the window): weights from the seed on the device, the
float32 reference's loss on the first batch and the program's own
dropout-free forward on it, one engine, the compiling first step and
``warmup_steps - 1`` more, each waited for.  Window: steps until
``--seconds`` have passed, dispatched as a trainer dispatches them:
``engine.train_batch`` returns the loss as a device value without
waiting (the engine's own design: it syncs only at ``steps_per_print``),
and the runner keeps ``steps_in_flight`` of them queued, fetching each
loss (``block_until_ready``) that many steps late.  The device so finds
its next step waiting whenever the host is slow to come back, and a
stalled host thread (the machine's cores are shared; stalls of over two
seconds were read) costs nothing until the queue runs dry.  Every step dispatched is waited for and
counted, and the window runs to the last one's end.  The host builds
each batch inside the window, as a trainer does.
"""
import collections
import dataclasses
import os
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.lib import flops, reference, traffic as traffic_lib, xplane
from perfbench.runners import _common

# The program's forward (bf16 compute, bf16 weights rounded from the float32
# init, its kernels, dropout off) against the float32 reference at full
# precision, same rows, same init: bf16's 8 mantissa bits put each logit
# off by ~0.4% of its size, which averages out over 4096+ tokens of a loss
# near ln(50304) = 10.8; what is left is a bias of a few 1e-3.  Computing
# in 8-bit floats, or leaving out a layer, moves the loss by far more than
# 0.01.  Read on the chip: at most 0.00097 in 20 runs of both
# configurations (PR 23, then through the engine's first step at dropout 0).
LOSS_ATOL = 0.01
# The engine's first step runs the configuration's dropout (0.1 as
# published), which the reference does not.  At a random init the targets
# are independent of the logits, so dropout's noise moves the mean loss
# over 4096+ tokens by little: 0.001-0.043 read on the chip over eight
# seeds of both configurations (PR 23), spread like |N(0, 0.025)|.  0.15 is
# six of those; this is the coarse check that the TRAINING step computes
# this loss at all (a loss summed instead of averaged, or taken on other
# rows, is off by far more), the fine one is LOSS_ATOL above.
DROPOUT_LOSS_ATOL = 0.15
SPANS = ("data", "train_batch", "wait")


def _state_widths(engine):
    """(trained elements, bytes per param, bytes per moment) of the
    engine's state, for the optimizer kernel's least traffic."""
    params = [l for l in jax.tree_util.tree_leaves(engine.state.params)
              if jnp.issubdtype(l.dtype, jnp.floating)]
    moments = [l for l in jax.tree_util.tree_leaves(engine.state.opt_state)
               if getattr(l, "ndim", 0) >= 1]
    return (sum(int(l.size) for l in params),
            max(l.dtype.itemsize for l in params),
            max(l.dtype.itemsize for l in moments))


def run(ctx):
    import deepspeed_tpu
    from deepspeed_tpu.models import gpt2_loss_fn
    from deepspeed_tpu.parallel.topology import build_mesh

    sizes, tr = ctx.config, ctx.traffic
    chips = len(ctx.devices)
    seq = int(tr["seq_len"])
    cfg = _common.model_config(sizes, **sizes["train"]["model_options"])
    ds_config = dict(sizes["train"]["ds_config"])
    mbs = int(ds_config["train_micro_batch_size_per_gpu"])
    gas = int(ds_config["gradient_accumulation_steps"])
    rows = mbs * chips * gas
    ds_config["train_batch_size"] = rows
    tokens_per_step = rows * seq

    raw = np.frombuffer(open(os.path.join(ctx.root, tr["corpus"]),
                             "rb").read(), dtype=np.uint8)
    # Far more offsets than a window of real steps uses; a toy that steps
    # faster wraps around.
    offsets = traffic_lib.corpus_offsets(
        len(raw), ctx.seed, seq, rows, int(ctx.seconds * 50) + 64)

    def batch(i):
        return traffic_lib.corpus_batch(raw, offsets[i % len(offsets)], seq)

    # --- set-up: weights, reference loss on the first batch ---
    params = jax.block_until_ready(_common.seeded_params(cfg, ctx.seed))
    ctx.mark("weights")
    ref_fn = jax.jit(lambda p, r: reference.next_token_loss(
        p, r, num_heads=cfg.num_heads, eps=cfg.layer_norm_eps))
    first = batch(0)
    ref_loss = float(np.mean(np.concatenate(
        [np.asarray(ref_fn(params, first[i:i + 2]))
         for i in range(0, rows, 2)])))
    del ref_fn
    # The program's own loss function on the same rows with dropout off
    # (the engine's train and eval steps always run the configuration's
    # dropout), one device, compute-type weights.
    plain = jax.jit(lambda p, b: gpt2_loss_fn(dataclasses.replace(
        cfg, hidden_dropout=0.0, attn_dropout=0.0))(
            jax.tree_util.tree_map(lambda a: a.astype(cfg.dtype), p), b, None))
    plain_loss = float(plain(params, first))
    del plain
    ctx.mark("reference")

    engine, _, _, _ = deepspeed_tpu.initialize(
        config=ds_config, model=gpt2_loss_fn(cfg), model_params=params,
        mesh=build_mesh(devices=list(ctx.devices)))
    del params
    ctx.mark("engine")

    def dispatch(i):
        with jax.profiler.TraceAnnotation("data"):
            b = batch(i)
        with jax.profiler.TraceAnnotation("train_batch"):
            return engine.train_batch(b)

    def wait(loss):
        with jax.profiler.TraceAnnotation("wait"):
            return float(jax.block_until_ready(loss))

    in_flight = int(tr["steps_in_flight"])

    def pump(first, more):
        """Steps ``first``, ``first + 1``, ... while ``more(dispatched,
        queued)``, at most ``in_flight`` of them dispatched and not yet
        waited for; all are waited for before it returns their completion
        times."""
        queue, done, n = collections.deque(), [], first
        while True:
            while len(queue) < in_flight and more(n - first, len(queue)):
                queue.append(dispatch(n))
                n += 1
            if not queue:
                return done
            losses.append(wait(queue.popleft()))
            done.append(time.perf_counter())

    warm = int(tr["warmup_steps"])
    losses = []
    t = time.perf_counter()
    pump(0, lambda d, q: d < 1)
    first_step_s = time.perf_counter() - t
    ctx.mark("first_step")
    t = time.perf_counter()
    pump(1, lambda d, q: d < warm - 1)
    step_est_s = (time.perf_counter() - t) / max(1, warm - 1)
    compiles_setup = dict(ctx.compile_events)

    # --- the window.  A traced run cuts it in three: a few steps, the
    # profiler's steps (the queue empty at both ends, so that the trace
    # holds whole steps), the rest ---
    traced = int(tr["traced_steps"]) if ctx.trace else 0
    ctx.compile_events.clear()
    t_start = time.perf_counter()
    setup_s = t_start - ctx.t0
    done = []
    if traced:
        done += pump(warm, lambda d, q: d < 3)
        _common.start_trace(ctx.trace_dir)
        done += pump(warm + len(done), lambda d, q: d < traced)
        jax.profiler.stop_trace()
    # Another step while the queued ones should end inside the window.
    done += pump(warm + len(done), lambda d, q: time.perf_counter() - t_start
                 + q * step_est_s < ctx.seconds)
    window_s = done[-1] - t_start
    # Time from one step's end to the next one's.
    step_s = [b - a for a, b in zip([t_start] + done, done)]
    compiles_window = int(ctx.compile_events.get("n", 0))
    steps = len(step_s)

    tokens_per_s = tokens_per_step * steps / window_s
    fpt = flops.train_flops_per_token(sizes, seq)
    finite = [bool(np.isfinite(l)) for l in losses]
    loss_err = abs(plain_loss - ref_loss)
    step_err = abs(losses[0] - ref_loss)
    fell = float(np.mean(losses[-5:])) < losses[0]
    correct = all(finite) and loss_err <= LOSS_ATOL \
        and step_err <= DROPOUT_LOSS_ATOL and fell and compiles_window == 0

    n_elements, param_itemsize, moment_itemsize = _state_widths(engine)

    ctx.say(phase="train", model=cfg.name, micro_batch=mbs, chips=chips,
            seq=seq, tokens_per_step=tokens_per_step, steps=steps,
            steps_in_flight=in_flight,
            window_s=window_s, setup_s=setup_s, first_step_s=first_step_s,
            setup_marks_s=ctx.marks,
            compiles_setup=compiles_setup, compiles_window=compiles_window,
            step_ms_median=statistics.median(step_s) * 1e3,
            step_ms_min=min(step_s) * 1e3, step_ms_max=max(step_s) * 1e3,
            losses_first=losses[:4], losses_last=losses[-5:],
            reference_loss=ref_loss, dropout_free_loss=plain_loss,
            loss_abs_err=loss_err, loss_atol=LOSS_ATOL,
            first_step_abs_err=step_err,
            first_step_atol=DROPOUT_LOSS_ATOL, loss_fell=fell,
            dropout=[cfg.hidden_dropout, cfg.attn_dropout],
            train_tokens_per_s=tokens_per_s, flops_per_token=fpt,
            mfu=None if ctx.peaks is None else
            tokens_per_s * fpt / (chips * ctx.peaks["bf16_flops_per_s"]),
            n_param_elements=n_elements, param_itemsize=param_itemsize,
            moment_itemsize=moment_itemsize)

    return {
        "kind": "train", "correct": correct, "attempted": steps,
        "failed": finite[warm:].count(False),
        "end_to_end": {"train_tokens_per_s": tokens_per_s,
                       "setup_s": setup_s},
        "memory_peak_bytes": _common.memory_peak_bytes(ctx.devices),
        "step_s": step_s, "steps_traced": traced, "chips": chips,
        "peaks": ctx.peaks,
        "adam_bytes_per_step": flops.adam_step_bytes(
            n_elements, param_itemsize, moment_itemsize) / chips,
        "trace": xplane.reduce_trace(
            ctx.trace_dir, SPANS, "host", chips,
            cpu_rehearsal=ctx.rehearsal)
        if traced else None,
    }
