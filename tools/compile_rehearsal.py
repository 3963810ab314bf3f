"""Third rehearsal: compile the whole programs `chip_smoke.py` runs for a
DESCRIBED v5e, without a chip (on-chip-measurement guide, section 2).

    JAX_PLATFORMS=cpu python tools/compile_rehearsal.py

Compiles, with `chip_smoke.py`'s own model and ds_config (gpt2-large
1280x36, S=1024, micro-batch 4 per chip, ZeRO-2, master-free bf16 with
stochastic rounding, fused optimizer, ``fused_kernels`` auto):

- the one-chip train step, and the paged decode / speculative-verify /
  chunked-prefill serving programs with the Pallas paged-attention
  kernel;
- the dp=4 train step on the ``v5e:2x2`` topology;
- the four-chip run's one-device twin (4 accumulation steps, 5/6 depth);

and prints compile seconds, ``memory_analysis()``, the Pallas kernels
found in each program and, for 4 chips, the collectives.  The memory
verdict is the compiler's own: a program over the chip's usable HBM
raises RESOURCE_EXHAUSTED with its largest allocations (it matched the
chip's verdict to the MB; ``memory_analysis()``'s temp figure
over-counts and is only good for before/after).

A compile that passes is a compile, never a run.  Nothing here executes
on a device: the engine is built on the described devices, and every
``jax.jit`` call made WHILE it is being built is answered with
``jax.eval_shape`` (the real parameters are CPU arrays; the engine state
becomes shapes with the described devices' shardings).  That — and the
``jax.default_backend`` steer that puts the kernels on their TPU side —
is scaffolding of this script only; no program option exists for it.
"""
import collections
import math
import os
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P    # noqa: E402

from chip_smoke import (MICRO_BATCH, MODEL, model_config,     # noqa: E402
                        pallas_kernels, twin_config, write_ds_config)

HBM_BYTES = 16 * 2 ** 30
_real_jit = jax.jit


class _AbstractJit:
    """``jax.jit`` stand-in while an engine is built on described
    devices: a CALL answers with shapes (+ the declared out_shardings),
    ``lower`` is the real thing."""

    def __init__(self, fn, **kw):
        self._fn, self._kw = fn, kw
        self._jit = _real_jit(fn, **kw)

    def __call__(self, *args, **kwargs):
        out = jax.eval_shape(self._fn, *args, **kwargs)
        sh = self._kw.get("out_shardings")
        if sh is None:
            return out
        return jax.tree_util.tree_map(
            lambda s, o: jax.ShapeDtypeStruct(o.shape, o.dtype, sharding=s),
            sh, out, is_leaf=lambda x: isinstance(x, jax.sharding.Sharding))

    def __getattr__(self, name):
        return getattr(self._jit, name)


def report(name, compiled, seconds):
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    text = compiled.as_text()
    print(f"[{name}] compiled in {seconds:.1f}s; per-device bytes: "
          f"args={ma.argument_size_in_bytes:,} out={ma.output_size_in_bytes:,}"
          f" temp={ma.temp_size_in_bytes:,} alias={ma.alias_size_in_bytes:,}"
          f" -> live~{total / 2 ** 30:.2f} GiB of "
          f"{HBM_BYTES / 2 ** 30:.0f} GiB", flush=True)
    print(f"[{name}] pallas kernels: {pallas_kernels(text)}", flush=True)
    assert "tpu_custom_call" in text, f"{name}: no Pallas kernel compiled in"
    # The compiler itself refuses a program over the chip's usable HBM
    # (RESOURCE_EXHAUSTED, with the largest allocations listed); a pass
    # with this sum near 16 GiB is a program with little headroom.
    if total > 0.9 * HBM_BYTES:
        print(f"[{name}] NOTE: under 10% HBM headroom by this count",
              flush=True)
    return text


def train_step(devices, cfg, gas: int):
    import deepspeed_tpu
    # Imported here, before jax.jit is stood in for: the module wraps its
    # per-leaf update in a jit of its own when it is imported.
    import deepspeed_tpu.ops.fused_update  # noqa: F401
    from deepspeed_tpu.models import gpt2_init, gpt2_loss_fn
    from deepspeed_tpu.parallel.topology import build_mesh

    n = len(devices)
    mbs, S = MICRO_BATCH, cfg.max_seq_length
    workdir = tempfile.mkdtemp(prefix="compile_rehearsal_")
    ds_config = write_ds_config(workdir, n_replicas=n, gas=gas,
                                telemetry_dir=os.path.join(workdir, "tel"))
    with jax.default_device(jax.devices("cpu")[0]):
        params = gpt2_init(jax.random.PRNGKey(0), cfg)
    mesh = build_mesh(devices=list(devices))
    jax.jit = _AbstractJit
    try:
        engine, _, _, _ = deepspeed_tpu.initialize(
            config=ds_config, model=gpt2_loss_fn(cfg), model_params=params,
            mesh=mesh)
        step = engine._build_train_step()
    finally:
        jax.jit = _real_jit
    repl = NamedSharding(mesh, P())
    if n == 1:
        batch = jax.ShapeDtypeStruct((mbs * gas, S + 1), jnp.int32,
                                     sharding=repl)
    else:
        micro = jax.ShapeDtypeStruct((gas, mbs * n, S + 1), jnp.int32)
        batch = jax.ShapeDtypeStruct(
            micro.shape, micro.dtype,
            sharding=engine._batch_sharding(micro, leading_dims=2))
    rng = jax.ShapeDtypeStruct(engine._base_rng.shape,
                               engine._base_rng.dtype, sharding=repl)
    t0 = time.perf_counter()
    compiled = step.lower(engine.state, batch, rng).compile()
    text = report(f"train_step dp={n} gas={gas} layers={cfg.num_layers}",
                  compiled, time.perf_counter() - t0)
    if n > 1:
        from deepspeed_tpu.parallel.hlo_audit import parse_hlo_collectives
        kinds = collections.Counter(
            (o.kind, o.in_loop) for o in parse_hlo_collectives(text))
        print(f"[train_step dp={n}] collectives (kind, in_loop): "
              f"{dict(kinds)}; engine grad_sync={engine._grad_sync_mode}",
              flush=True)
        assert any(k == "reduce-scatter" for k, _ in kinds) or \
            any(k == "all-reduce" for k, _ in kinds)


def serving_steps(device, cfg):
    from deepspeed_tpu.inference import decode as dm
    from deepspeed_tpu.models import gpt2_init
    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(device)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            tree)

    params = on_chip(jax.eval_shape(
        lambda k: jax.tree_util.tree_map(
            lambda p: p.astype(cfg.dtype), gpt2_init(k, cfg)),
        jax.random.PRNGKey(0)))
    from deepspeed_tpu.analysis.hlo_text import ops_in_units_of
    from deepspeed_tpu.inference import kv_cache
    slots, bs, max_len, spec_k, chunk = 8, 16, 1024, 4, 32
    J = max_len // bs
    spec = kv_cache.PagedKVCacheSpec(
        num_layers=cfg.num_layers, num_slots=slots, num_blocks=slots * J,
        block_size=bs, max_len=max_len, num_heads=cfg.num_heads,
        head_dim=cfg.head_dim, dtype=cfg.dtype)
    pool = on_chip(jax.ShapeDtypeStruct(spec.shape, spec.dtype))
    i32 = lambda *shape: on_chip(jax.ShapeDtypeStruct(shape, jnp.int32))

    served = dm.GPT2Served(cfg)
    flat = lambda out, pools, _: (out, *pools)                # noqa: E731

    def prefill_chunk_with_head(p, kc, vc, t, bt, st, li, ac):
        # (the hidden row, then the head: what a chunk program that ends a
        # prompt runs — the engine puts the head under a branch)
        h, pools, _ = served.prefill_chunk(
            p, (kc, vc), t, bt, st, li, ac, paged_kernel=True)
        return served.head(p, h), *pools

    programs = {
        "paged_decode": (
            lambda p, kc, vc, t, l, bt: flat(*served.decode(
                p, (kc, vc), t, l, bt, num_groups=1, paged_kernel=True)),
            (params, pool, pool, i32(slots), i32(slots), i32(slots, J))),
        f"paged_verify_k{spec_k + 1}": (
            lambda p, kc, vc, t, l, bt: flat(*served.verify(
                p, (kc, vc), t, l, bt, num_groups=1, paged_kernel=True)),
            (params, pool, pool, i32(slots, spec_k + 1), i32(slots),
             i32(slots, J))),
        f"paged_prefill_chunk{chunk}": (
            prefill_chunk_with_head,
            (params, pool, pool, i32(1, chunk), i32(1, J), i32(1), i32(1),
             on_chip(jax.ShapeDtypeStruct((1,), jnp.bool_)))),
    }
    for name, (fn, args) in programs.items():
        t0 = time.perf_counter()
        compiled = jax.jit(fn, donate_argnums=(1, 2)).lower(*args).compile()
        text = report(name, compiled, time.perf_counter() - t0)
        # The write is in place: nothing but parameters, tuples, the layer
        # loop, bitcasts and the aliased kernels is as large as a layer
        # of the pool, and both pools are aliased.
        handled = collections.Counter(
            op for op, _ in ops_in_units_of(text, math.prod(spec.shape[2:])))
        print(f"[{name}] pool-sized instructions: {dict(handled)}; pool "
              f"{spec.nbytes():,} B, aliased "
              f"{compiled.memory_analysis().alias_size_in_bytes:,} B",
              flush=True)
        assert not set(handled) - {"parameter", "tuple", "while", "bitcast",
                                   "get-tuple-element", "custom-call"}


def main():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    jax.default_backend = lambda *a, **k: "tpu"
    cfg = model_config(MODEL)
    train_step(topo.devices[:1], cfg, gas=1)
    serving_steps(topo.devices[0], cfg)
    train_step(topo.devices[:4], cfg, gas=1)
    train_step(topo.devices[:1], twin_config(cfg), gas=4)
    print("compiled (not run): every program above accepted by the "
          "v5e compiler")


if __name__ == "__main__":
    main()
