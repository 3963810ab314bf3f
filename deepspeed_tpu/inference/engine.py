"""InferenceEngine — the serving-tier counterpart of the training
engine: checkpoint/params in, continuously-batched tokens out.

Architecture (mirrors the training engine's discipline):

- The MODEL comes in as a served model (inference/served.py): what it
  keeps in the paged pool — rows a token and layer, or a fixed-size
  state a stream (then a block is a page, and the prefix cache keeps
  snapshots: inference/kv_cache.py), in one class of layers or several
  (window layers keep only what is in reach: a pool, a table and an
  allocator a class) — and its decode / verify / prefill programs.
  Everything below — slots, admission, the allocator, the prefix cache,
  sampling, spans — is the same for every model.
- TWO compiled programs serve everything: ``decode_step`` (one token for
  every slot at once) and ``prefill_step`` (one chunk of one slot's
  prompt a dp group). ``decode_step`` has one abstract signature for
  the lifetime of the engine; ``prefill_step`` has one a ROW WIDTH of
  ``prefill_widths`` (``prefill_chunk`` and its half, no narrower than
  128 rows: 512 -> 256, 512), every one compiled before the first
  admission, and a dispatch takes the narrowest that holds its rows (a
  tail's last chunk is short; its dead rows would run every GEMM). Both
  are wrapped by the recompile sentinel; ``fail_on_recompile`` turns any
  post-warmup retrace — any other shape — into a hard error. Request
  admission, progress, and eviction never touch a compiled shape.
  A program's last scopes run only where their result is read: a chunk
  program computes the model's head and samples only in the dispatch
  that ends a prompt (a branch on an operand the host sets; the others
  return zeros nobody fetches), and ``sample_tokens`` branches on the
  traced temperature (a greedy step draws no noise).
- The KV cache (inference/kv_cache.py) is born sharded: slots over the
  mesh data axis, heads over the model axis. Its buffers are DONATED
  through every step, so the cache exists once — and a step writes
  its new K/V rows into those buffers where they lie (no program
  slices, relays or rewrites the pool: a step's cost does not depend on
  ``num_blocks``).
- Host-side per-slot counters (lengths, active, last token) are the
  scheduler's state; they enter each step as tiny int arrays. The one
  device fetch per decode iteration is the sampled-token readback (the
  host must see tokens to hand them out and to detect EOS), and it is
  the ONLY one. The next step does not wait for it: ``decode_step``
  takes its tokens where they lie — the previous execution's fetch
  array, still on the device — so the scheduler's loop dispatches
  iteration n+1 BEFORE it fetches n's tokens (``decode_once`` with
  ``continuing``), and the fetch, the emission and the host's
  bookkeeping run under n+1's device time.
- Telemetry rides the training spine unchanged: per-iteration step
  records (occupancy, active slots, fenced step wall), ``prefill``
  spans, ``request_complete`` events, and the ``ServingAggregator``
  snapshot (TTFT/TPOT p50/p95, tokens/s) in every drain's report
  record. ``tools/telemetry_report.py`` turns the stream into the
  ``serving`` section benches and CI diff.
- Weight quantization (``inference.quantize``): bf16 via the stochastic
  -rounding machinery, or int8-at-rest with in-step dequantize
  (inference/quantize.py).
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import os
import pickle
import tempfile
import weakref
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import kv_cache
from .quantize import (dequantize, quantize_params, quantized_bytes,
                       resolve_kv_dtype)
from .served import (ServedModel, filter_rows_lowered, head_and_sample,
                     sample_tokens, served_model, spec_accept, split_counters,
                     with_counters)
from .spec import NGramDrafter
from .. import constants as C
from ..monitor import Telemetry
from ..monitor.telemetry import ids_arg
from ..monitor.memory import analytic_state_bytes
from ..monitor.serving import ServingAggregator
from ..monitor.serving_slo import ServingGoodputLedger, SLOTracker
from ..ops import paged_attention as paged_attn_ops
from ..parallel.topology import build_mesh, DP_AXIS, MP_AXIS, SP_AXIS
from ..runtime.config import InferenceConfig, TelemetryConfig
from ..runtime.config_utils import load_config_json
from ..utils.logging import log_dist, logger

try:
    from flax import serialization as flax_serialization
except Exception:  # pragma: no cover
    flax_serialization = None


# The least row width of a chunked-prefill program. v5e's ridge is 197
# TFLOP/s over 819 GB/s = 240 FLOP a byte = 240 rows of a bf16 GEMM: at
# 128 rows a GEMM is already bound by its weights' bytes, so a narrower
# program would cost the same and only add a compile.
MIN_PREFILL_WIDTH = 128
# How many widths the program is built at. Every width is a whole
# program: compiled at the first start (10-16 s on the chip), kept
# (14-23 MB beside the compile cache, ``_WidthPrograms``) and loaded at
# every later one — or traced and lowered again, 0.75-7 s by served
# family, where nothing is kept (PERF.md section 6, PR 43). The first
# halving is the one that pays: it holds nine short tails in ten and
# takes most of what the dead rows cost.
MAX_PREFILL_WIDTHS = 2


def prefill_widths(prefill_chunk: int, block_size: int) -> Tuple[int, ...]:
    """The row widths ``prefill_step`` is compiled at, ascending:
    ``prefill_chunk``, then its halvings — ``MAX_PREFILL_WIDTHS`` widths
    at most — while the result is at least ``MIN_PREFILL_WIDTH`` and a
    multiple of ``block_size`` (512 -> (256, 512); 128 or 96 ->
    themselves)."""
    widths = [prefill_chunk]
    while len(widths) < MAX_PREFILL_WIDTHS:
        half, odd = divmod(widths[-1], 2)
        if odd or half < MIN_PREFILL_WIDTH or half % block_size:
            break
        widths.append(half)
    return tuple(reversed(widths))


@functools.lru_cache(maxsize=None)
def _build_digest() -> str:
    """What a compiled program depends on besides its own module: this
    package's sources and the compiler's versions."""
    import jaxlib
    h = hashlib.sha256(repr((
        jax.__version__, jaxlib.__version__,
        jax.devices()[0].client.platform_version)).encode())
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for d, dirs, files in os.walk(root):
        dirs.sort()
        for f in sorted(files):
            if f.endswith(".py"):
                path = os.path.join(d, f)
                h.update(os.path.relpath(path, root).encode())
                with open(path, "rb") as fh:
                    h.update(fh.read())
    return h.hexdigest()


def _programs_dir() -> Optional[str]:
    """Where ``_WidthPrograms`` keeps executables: beside the compile
    cache, if the program turned that on."""
    cache = jax.config.jax_compilation_cache_dir
    return os.path.join(cache, "prefill_widths") if cache else None


class _WidthPrograms:
    """``prefill_step`` at several row widths, called like the jitted
    function it holds: one compiled executable a width, kept here and
    not in ``jax.jit``'s own cache, because a further width is a WHOLE
    program traced and lowered again at every start — seconds of Python
    that the compile cache does not save (4.7 s of cell 6's 63 s of
    set-up on the chip: PERF.md section 6, PR 43). So where the
    compile cache is on, a width after the first leaves its executable
    serialized beside it (``<cache>/prefill_widths/``) and the next
    start loads that: no trace, no lowering. The file's name holds what
    the program depends on: the FIRST width's lowered module — built as
    ``jax.jit`` would at every start, and changed by whatever changes
    the function, its constants, the shapes and shardings of its
    arguments or the flags it is traced under — with this package's
    sources and the compiler's versions (``_build_digest``). A file that
    does not load is built again. ``_cache_size`` (the widths built so
    far) is what the recompile sentinel watches, ``lower`` what the
    lint audit re-lowers with."""

    def __init__(self, jitted: Callable, width_arg: int, devices):
        self.jitted, self.width_arg = jitted, width_arg
        self.devices = list(devices)
        self.lower = jitted.lower
        self.__name__ = getattr(jitted, "__name__", "prefill_step")
        self.execs: Dict[int, Any] = {}
        self.first: Optional[str] = None     # digest of the first module

    def _cache_size(self) -> int:
        return len(self.execs)

    def __call__(self, *args):
        tokens = args[self.width_arg]
        if isinstance(tokens, jax.core.Tracer):
            return self.jitted(*args)    # (an audit tracing through it)
        exe = self.execs.get(tokens.shape[1])
        if exe is None:
            exe = self.execs[tokens.shape[1]] = self._build(args)
        return exe(*args)

    def _build(self, args):
        if self.first is None:
            lowered = self.jitted.lower(*args)
            self.first = hashlib.sha256(
                (_build_digest() + lowered.as_text()).encode()).hexdigest()
            return lowered.compile()
        where = _programs_dir()
        if where is None:
            return self.jitted.lower(*args).compile()
        from jax.experimental import serialize_executable
        path = os.path.join(
            where, f"{self.first}-{args[self.width_arg].shape[1]}")
        try:
            with open(path, "rb") as fh:
                return serialize_executable.deserialize_and_load(
                    *pickle.load(fh), execution_devices=self.devices)
        except FileNotFoundError:
            pass
        except Exception as e:               # unreadable: build it again
            logger.warning(f"prefill_step: {path} did not load ({e!r})")
        exe = self.jitted.lower(*args).compile()
        try:
            os.makedirs(where, exist_ok=True)
            fd, tmp = tempfile.mkstemp(dir=where)
            with os.fdopen(fd, "wb") as fh:
                pickle.dump(serialize_executable.serialize(exe), fh)
            os.replace(tmp, path)
        except Exception as e:               # a cache, not a dependency
            logger.warning(f"prefill_step: {path} not written ({e!r})")
        return exe


@dataclasses.dataclass
class _Flight:
    """One decode iteration between its dispatch and its token fetch."""
    fetch: Any                  # device: tokens (+ the model's counters)
    logits: Any                 # device: [S, V]
    probes: Any                 # device: the model's probes (or None)
    mask: np.ndarray            # [S] bool: slots in it and still owed its token
    n_active: int               # slots it was dispatched for
    t0: float                   # the clock when its host work began ...
    prefill_s: float            # ... and the prefill seconds spent until then
    ahead: int                  # dispatched with the one before unfetched
    cache_bytes: int
    context_tokens: int
    dropped: int = 0            # rows computed for streams released since


class InferenceEngine:
    """Batched autoregressive serving over a device mesh."""
    # The served model's probes (``ServedModel.probe_names``) of the
    # program(s) whose logits a caller last asked for (``return_logits``),
    # by name: a row a slot after ``decode_once``, a row an admission after
    # ``prefill_many``.  None for a model without.
    last_probes: Optional[Dict[str, np.ndarray]] = None

    def __init__(self, model_cfg: Any, params: Any,
                 config: Any = None, mesh: Optional[Mesh] = None,
                 rng: Optional[jax.Array] = None,
                 param_shardings: Any = None):
        if isinstance(config, str):
            config = load_config_json(config)
        config = dict(config or {})
        # A ServedModel, or a model config an implementation is
        # registered for.
        self.model_cfg = model_cfg
        served = self._served = served_model(model_cfg)
        self.icfg = InferenceConfig(config)
        self.tcfg = TelemetryConfig(config)
        self.mesh = mesh if mesh is not None else build_mesh()
        self.dp = int(self.mesh.shape.get(DP_AXIS, 1))
        self.mp = int(self.mesh.shape.get(MP_AXIS, 1))
        self.sp = int(self.mesh.shape.get(SP_AXIS, 1))

        # --- static serving geometry (all of it compiled-program shape) ---
        self.max_slots = int(self.icfg.max_slots)
        self.max_len = int(self.icfg.max_seq_len) or \
            int(served.max_positions)
        if self.max_len > served.max_positions:
            raise ValueError(
                f"inference.max_seq_len={self.max_len} exceeds the model's "
                f"position table ({served.max_positions})")
        self.prefill_chunk = int(self.icfg.prefill_chunk)
        if self.max_len % self.prefill_chunk:
            raise ValueError(
                f"inference.prefill_chunk={self.prefill_chunk} must divide "
                f"the cache capacity ({self.max_len}) — padded prompts "
                "would otherwise overrun the slot")
        self.block_size = int(self.icfg.block_size)
        self.prefill_widths = prefill_widths(self.prefill_chunk,
                                             self.block_size)
        self._prefill_warmed = len(self.prefill_widths) < 2
        # The model's own word, read once: its chunk program leaves a
        # snapshot at a row inside the chunk (else the plan cuts there).
        self._freeze_in_chunk = bool(served.freezes_in_chunk)
        self.spec_k = int(self.icfg.spec_k)
        self.replica = str(self.icfg.replica)
        # Pallas paged-attention kernel vs the one-hot pool contraction.
        # Resolved ONCE here: the compiled paths bake the choice in, so
        # flipping the env var mid-flight cannot desync the sentinel.
        self.paged_kernel = bool(paged_attn_ops.paged_kernel_enabled(
            self.icfg.paged_kernel))
        # 1 once the decode program is traced with a per-stream class's
        # filter rows rewritten in place (``served.filter_rows``); the
        # ``decode`` span carries it beside ``state_pages_live``.
        self.filter_rows_in_place = 0

        # --- weights: quantize, then commit to the mesh ---
        self.quantize = self.icfg.quantize
        self._base_rng = rng if rng is not None else jax.random.PRNGKey(17)
        if self.quantize != "none" and param_shardings is not None:
            raise NotImplementedError(
                "inference.quantize does not compose with tensor-parallel "
                "param_shardings yet (quantized leaves change the tree "
                "structure the specs address)")
        params = quantize_params(params, self.quantize, self._base_rng)
        if param_shardings is not None:
            shardings = jax.tree_util.tree_map(
                lambda spec: NamedSharding(self.mesh, spec),
                param_shardings)
        else:
            shardings = NamedSharding(self.mesh, P())
        self._params = jax.device_put(params, shardings)
        self.param_bytes = quantized_bytes(self._params)

        # --- the KV cache: the paged block pool, born sharded ---
        kv_dtype = resolve_kv_dtype(self.icfg.kv_cache_dtype, served.dtype)
        # One spec, pool set and allocator a CLASS of cache layers (one
        # class for most models; kv_cache.py's docstring), each built
        # from the model's answer for THAT class: a state's page is not a
        # K/V block's tile, nor of its dtype where the model says so.
        classes = served.cache_classes
        asked = self.icfg.num_blocks
        if asked and isinstance(asked, dict) != (len(classes) > 1):
            raise ValueError(
                f"inference.num_blocks={asked!r}: {served.name} keeps "
                + (f"its cache layers in classes "
                   f"{[c.name for c in classes]} and takes "
                   "{class name: blocks}" if len(classes) > 1 else
                   "one class of cache layers and takes an int"))
        specs = kv_cache.class_specs(
            classes, asked, rows=max(self.prefill_chunk, self.spec_k + 1),
            of_class=lambda cls: served.class_geometry(cls, self.block_size),
            num_slots=self.max_slots, block_size=self.block_size,
            max_len=self.max_len, num_groups=self.dp, dtype=kv_dtype)
        self.allocator = kv_cache.allocator_for(specs, self.spec_k)
        self.cache_specs = specs
        self.cache_spec = specs[0]
        self.num_blocks = sum(sp.num_blocks for sp in specs)
        served.table_widths = tuple(sp.max_blocks_per_slot for sp in specs)
        self.cache, self._cache_sh = {}, {}
        for spec in specs:
            self.cache.update(kv_cache.init_paged_cache(spec, self.mesh))
            self._cache_sh.update(kv_cache.paged_shardings(
                self.mesh, spec.pool_names))
        self.block_tables = np.full(
            (self.max_slots, self.allocator.table_width),
            kv_cache.DEAD_BLOCK, np.int32)
        self.drafter = NGramDrafter(self.spec_k, self.icfg.spec_ngram) \
            if self.spec_k > 0 else None
        self._spec_proposed = 0
        self._spec_accepted = 0

        # --- host-authoritative per-slot counters ---
        self.lengths = np.zeros(self.max_slots, np.int32)
        self.active = np.zeros(self.max_slots, bool)
        self.last_tokens = np.zeros(self.max_slots, np.int32)
        # The decode iteration dispatched and not yet fetched (at most
        # one: ``decode_once`` with ``continuing``), the slots activated
        # since the last dispatch (their token is on the host, out of
        # ``prefill_fetch``), and what a first dispatch takes for the
        # previous execution's fetch array: zeros, committed like a
        # program's output so that every dispatch is one compiled form.
        self._inflight: Optional[_Flight] = None
        self._fresh = np.zeros(self.max_slots, bool)
        self._fetch_sh = NamedSharding(self.mesh, P())
        self._no_fetch = jax.device_put(
            np.zeros(self.max_slots + len(served.counter_names), np.int32),
            self._fetch_sh)
        # Clock of the latest iteration's tokens and the prefill seconds
        # spent by then (``decode_step_ms`` runs from there, less the
        # admissions between).
        self._t_tokens = (float("-inf"), 0.0)
        self._prefill_wall = 0.0
        self._held = set()               # acquired, not yet activated
        self._last_admit: Dict[int, Dict[str, Any]] = {}
        # Why the most recent select_slot returned None ("no_slot" =
        # every slot busy; "reservation" = slots free but the block-pool
        # gate refused the HBM booking). Host state for the scheduler's
        # rejection accounting.
        self.last_admit_block: Optional[str] = None

        # --- telemetry on the shared spine ---
        self.iterations = 0
        self._rng_calls = 0
        self.serving = ServingAggregator(self.max_slots,
                                         label=self.replica or None)
        self._attach_slo_overlays()
        tel_meta = dict(mode="serving", model=served.name,
                        dp=self.dp, mp=self.mp, sp=self.sp,
                        max_slots=self.max_slots, max_seq_len=self.max_len,
                        prefill_chunk=self.prefill_chunk,
                        block_size=self.block_size,
                        num_blocks=self.num_blocks,
                        spec_k=self.spec_k,
                        replica=self.replica,
                        quantize=self.quantize,
                        precision=jnp.dtype(served.dtype).name,
                        param_bytes=self.param_bytes,
                        kv_cache_bytes=sum(sp.nbytes() for sp in specs))
        if len(specs) > 1:
            tel_meta["cache_classes"] = {
                sp.name: {"layers": sp.num_layers, "reach": sp.reach,
                          "num_blocks": sp.num_blocks,
                          "table_blocks": sp.max_blocks_per_slot,
                          "bytes": sp.nbytes()} for sp in specs}
        # Analytic attend pricing (both ways, per generated token at
        # the bounds): the kernel term scales with live context
        # (ceil(ctx/bs)*bs — quoted at ctx = max_seq_len), the
        # one-hot term with pool CAPACITY. Projections, not device
        # measurements.
        self.serving.attend_mode = ("kernel" if self.paged_kernel
                                    else "onehot")
        sp_ = self.cache_spec
        tel_meta["paged_kernel"] = self.paged_kernel
        # The write always engages (one path), so its counter is
        # static: rows go into the donated pool in place, one block
        # tile read and written per row and pool. What the compiled
        # programs make of it (pools aliased, no pool-sized op) is
        # asserted in tests/test_tpu_compile.py.
        tel_meta["kv_write"] = {
            "mode": "in_place", "fold": sp_.fold,
            "pool_shape": list(sp_.shape),
            "pools": {n: list(sh) for n, sh in sp_.pool_shapes.items()},
            "tile_bytes": sp_.block_nbytes()
            // (len(sp_.pool_names) * sp_.num_layers),
            "rows_per_decode": self.max_slots * (self.spec_k + 1)}
        tel_meta["attend_flops_per_token"] = {
            "live_ctx_max": self._attend_cost(context=sp_.max_len)[0],
            "pool_capacity": self._attend_cost(
                pool_blocks=sp_.blocks_per_group)[0],
            "projection": "analytic"}
        tel_meta["attend_hbm_bytes_per_token"] = {
            "live_ctx_max": self._attend_cost(context=sp_.max_len)[1],
            "pool_capacity": self._attend_cost(
                pool_blocks=sp_.blocks_per_group)[1],
            "projection": "analytic"}
        self.telemetry = Telemetry(
            self.tcfg, default_report_steps=50, meta=tel_meta)
        _ref = weakref.ref(self)
        self.telemetry.step_provider = lambda: (
            _ref().iterations if _ref() is not None else -1)
        self.telemetry.set_analytic_footprint(analytic_state_bytes(
            {"params": self._params, "cache": self.cache}))

        # --- the compiled paths (sentinel-instrumented): decode, prefill,
        # the copy-on-write block copy and, with spec_k > 0, the
        # speculative verify step. Each has ONE abstract signature for
        # the engine's lifetime; chunked prefill one a width of
        # ``prefill_widths`` ---
        self._decode_fn = self.telemetry.instrument_step_fn(
            "decode_step", self._build_decode_step())
        prefill_step = self._build_prefill_step()
        if len(self.prefill_widths) > 1:     # tokens follow params, pools
            prefill_step = _WidthPrograms(
                prefill_step, 1 + len(self._cache_sh),
                self.mesh.devices.flat)
        self._prefill_fn = self.telemetry.instrument_step_fn(
            "prefill_step", prefill_step,
            signatures=len(self.prefill_widths))
        self._copy_fn = self.telemetry.instrument_step_fn(
            self.allocator.copy_program[0],
            self._build_copy(*self.allocator.copy_program))
        if self.spec_k > 0:
            self._verify_fn = self.telemetry.instrument_step_fn(
                "verify_step", self._build_verify_step())

        log_dist(
            f"InferenceEngine initialized: {served.name}, "
            f"slots={self.max_slots} (dp={self.dp}), "
            f"cache=paged bs={self.block_size} x{self.num_blocks} blocks "
            f"{self.max_len}x{served.cache_heads}h "
            f"({sum(sp.nbytes() for sp in specs) / 2 ** 20:.1f} MiB "
            f"{'+'.join(self._cache_sh)}), "
            f"prefill=chunk {self.prefill_chunk}, "
            f"spec_k={self.spec_k}, quantize={self.quantize}"
            + (f", replica={self.replica}" if self.replica else ""),
            ranks=[0])

    # ------------------------------------------------------------------ #
    # Compiled-path builders
    # ------------------------------------------------------------------ #
    @property
    def served(self) -> ServedModel:
        """The served model behind ``model_cfg`` (resolved where asked:
        the builders run on engine shells that hold a config only)."""
        return self.__dict__.get("_served") or served_model(self.model_cfg)

    @property
    def _attend_specs(self) -> Tuple[kv_cache.PagedKVCacheSpec, ...]:
        """The classes an attend walks: what the analytic attend counters
        and ``context_tokens_in_reach`` price."""
        return kv_cache.attended_specs(self.cache_specs)

    def _pools(self) -> Tuple[jax.Array, ...]:
        """The cache's pools in the served model's order."""
        return tuple(self.cache[name] for name in self._cache_sh)

    def _store_pools(self, pools) -> None:
        for name, pool in zip(self._cache_sh, pools):
            self.cache[name] = pool

    def _runtime_params(self, params):
        """Dequantize inside the compiled program (int8 at rest,
        compute-dtype transients); identity for none/bf16."""
        if self.quantize == "int8":
            return dequantize(params, self.served.dtype)
        return params

    def _jit_step(self, step: Callable, fetch_sharding=None) -> Callable:
        """``step(params, *pools, ...) -> (*pools, fetch, logits[,
        probes])``: the pools donated and returned where they lie; the
        last output only from a model with ``probe_names``."""
        sh = tuple(self._cache_sh.values())
        probes = (None,) * bool(self.served.probe_names)
        return jax.jit(step, donate_argnums=tuple(range(1, 1 + len(sh))),
                       out_shardings=sh + (fetch_sharding, None) + probes)

    def _outputs(self, out) -> Tuple[Any, Any, Any, Any]:
        """(pools, fetch, logits, probes or None) of a step's outputs."""
        n = len(self._cache_sh)
        return (out[:n], out[n], out[n + 1],
                out[n + 2] if len(out) > n + 2 else None)

    def _build_decode_step(self) -> Callable:
        """``decode_step(params, *pools, previous, tokens, fresh, lengths,
        block tables, key, temperature)``: a slot's input token is the
        host's (``tokens``) where ``fresh`` marks it, else the one the
        previous execution sampled for it, read from that execution's
        fetch array where it lies (``previous``: tokens, then the
        model's counters). The fetch array leaves the program under the
        sharding a first dispatch's zeros are committed with, so every
        dispatch runs the one compiled form."""
        served = self.served
        n = len(self._cache_sh)

        def decode_step(params, *args):
            pools, (previous, tokens, fresh, lengths, bt, key,
                    temperature) = args[:n], args[n:]
            tokens = jnp.where(fresh, tokens,
                               previous[:tokens.shape[0]])
            p = self._runtime_params(params)
            in_place = filter_rows_lowered["in_place"]
            logits, pools, counters, *probes = served.decode(
                p, pools, tokens, lengths, bt, num_groups=self.dp,
                paged_kernel=self.paged_kernel, mesh=self.mesh)
            # (this body runs when the program is traced: once)
            self.filter_rows_in_place = int(
                filter_rows_lowered["in_place"] > in_place)
            sampled = sample_tokens(logits, key, temperature)
            return (*pools, with_counters(sampled, counters), logits,
                    *probes)

        return self._jit_step(decode_step, self.__dict__.get("_fetch_sh"))

    def _build_prefill_step(self) -> Callable:
        """Group-batched chunked prefill: one chunk of one slot per dp
        group (single admissions leave the other groups' rows DEAD —
        uniform program, writes land nowhere). ``tokens`` is ``[G,
        width]``, one of ``prefill_widths``: the same function, compiled
        once a width (``_warm_prefill_widths``). A model that freezes its
        state inside a chunk (``ServedModel.freezes_in_chunk``) takes two
        more ``[G]`` operands after ``active``, the snapshot's row and
        page; no other model's program has them. ``read`` (a scalar
        int32) says whether the dispatch ENDS some group's prompt: only
        then does the program run the model's head and sample
        (``served.head_and_sample``: a branch on the operand); any other
        dispatch returns zeros for its tokens and logits, which nobody
        fetches. The model's counters ride the fetch array either way."""
        served = self.served
        n = len(self._cache_sh)

        def prefill_step(params, *args):
            pools, (tokens, bt_rows, start, last_idx, active, *freeze, read,
                    key, temperature) = args[:n], args[n:]
            p = self._runtime_params(params)
            h_last, pools, counters, *probes = served.prefill_chunk(
                p, pools, tokens, bt_rows, start, last_idx, active, *freeze,
                paged_kernel=self.paged_kernel, mesh=self.mesh)
            sampled, logits = head_and_sample(
                read, functools.partial(served.head, p), h_last, key,
                temperature)
            return (*pools, with_counters(sampled, counters), logits,
                    *probes)

        return self._jit_step(prefill_step)

    def _build_verify_step(self) -> Callable:
        """Speculative draft-then-verify: one batched K=spec_k+1 step,
        in-graph acceptance (served.spec_accept), ONE [S, K+2] int32
        readback — the same single host fetch per iteration plain
        decode pays."""
        served = self.served
        n = len(self._cache_sh)

        def verify_step(params, *args):
            pools, (tokens, lengths, bt, key, temperature) = \
                args[:n], args[n:]
            p = self._runtime_params(params)
            # (A model's counters are not fetched on this path.)
            logits, pools, _ = served.verify(
                p, pools, tokens, lengths, bt, num_groups=self.dp,
                paged_kernel=self.paged_kernel, mesh=self.mesh)
            out = spec_accept(logits, tokens, key, temperature)
            return (*pools, out, logits)

        return self._jit_step(verify_step)

    def _build_copy(self, name: str, scope: str) -> Callable:
        """The cache's one device copy (``kv_cache.copy_pages``): block
        ``src[g]`` to block ``dst[g]`` of every group, every layer, in
        the donated pools — a copy-on-write fork of a block, a snapshot
        into a stream's own page at admission, a stream's page into a
        snapshot when prefill reaches its boundary (a model whose chunk
        program cannot leave it itself). ``name`` and ``scope``
        are the allocator's (``copy_program``), and so are the pools it
        copies in (``copy_pools``: of a model's classes the one that
        copies; a block id means nothing in another class's pools), the
        others passing through where they lie."""
        sh = tuple(self._cache_sh.values())
        allocator = self.__dict__.get("allocator")   # (none: a shell)
        mine = [allocator is None or name in allocator.copy_pools
                for name in self._cache_sh]

        def copy(*args):
            pools, (src, dst) = args[:len(sh)], args[len(sh):]
            with jax.named_scope(scope):
                return tuple(kv_cache.copy_pages(pool, src, dst, self.mesh)
                             if copied else pool
                             for pool, copied in zip(pools, mine))

        copy.__name__ = name
        return jax.jit(copy, donate_argnums=tuple(range(len(sh))),
                       out_shardings=sh)

    def _next_key(self) -> jax.Array:
        """The key of the next program DISPATCHED: ``fold_in(base, call
        number)``. (A prefill and a decode that swap their order of
        dispatch swap their keys: with ``temperature`` > 0 the draws of
        a loop that dispatches ahead come from the same sequence of
        keys as a synchronous loop's, not key for key to the same
        program.)"""
        self._rng_calls += 1
        return jax.random.fold_in(self._base_rng, self._rng_calls)

    # ------------------------------------------------------------------ #
    # Slot lifecycle (host counters + block accounting — no device work)
    # ------------------------------------------------------------------ #
    def activate_slot(self, slot: int, context_len: int,
                      last_token: int) -> None:
        """Mark a freshly prefilled slot live: the cache holds positions
        0..context_len-1 and ``last_token`` decodes at position
        context_len next step."""
        self.lengths[slot] = int(context_len)
        self.active[slot] = True
        self.last_tokens[slot] = int(last_token)
        self._fresh[slot] = True
        self._held.discard(slot)
        if self.drafter is not None:
            self.drafter.observe(slot, [int(last_token)])

    def release_slot(self, slot: int) -> None:
        """Evict: counters clear and every block reference drops —
        private blocks return to the free list, prefix blocks whose
        refcount hits zero are LRU-retained for future hits. The stale
        rows are dead by masking. A row the iteration in flight computes
        for it is DROPPED: nobody is owed its token (it wrote into the
        stream's own block or page, which dies here; whatever takes the
        block next is dispatched behind it)."""
        flight = self._inflight
        if flight is not None and flight.mask[slot]:
            flight.mask[slot] = False
            flight.dropped += 1
        self.active[slot] = False
        self.lengths[slot] = 0
        self.last_tokens[slot] = 0
        self._held.discard(slot)
        row = self.block_tables[slot]
        self.allocator.release(slot, row)
        row[:] = kv_cache.DEAD_BLOCK
        if self.drafter is not None:
            self.drafter.reset(slot)

    def context_len(self, slot: int) -> int:
        return int(self.lengths[slot])

    @property
    def active_slots(self) -> int:
        return int(self.active.sum())

    @property
    def spec_enabled(self) -> bool:
        return self.spec_k > 0

    # ------------------------------------------------------------------ #
    # Admission (the scheduler's gate): slot occupancy AND HBM blocks
    # ------------------------------------------------------------------ #
    def group_of(self, slot: int) -> int:
        """The dp group (pool shard) a slot's blocks live in."""
        return slot // self.cache_spec.slots_per_group

    def select_slot(self, prompt: Sequence[int],
                    max_new_tokens: int = 0,
                    exclude_groups: Optional[set] = None
                    ) -> Optional[int]:
        """Pick and HOLD a free slot for this prompt, or None when the
        engine cannot admit it now.

        The gate is slot occupancy AND HBM accounting: a group must
        cover the request's worst-case block need
        (the allocator's ``can_admit``), and among admissible
        groups the one already holding the longest cached prefix of
        this prompt wins (prefix affinity — the request lands where its
        blocks live), ties broken toward the most available HBM. The
        hold is released by ``activate_slot`` or ``release_slot``.
        ``exclude_groups`` lets the scheduler gather a one-slot-per-
        group admission batch for ``prefill_many``."""
        self.last_admit_block = None
        free = [s for s in range(self.max_slots)
                if not self.active[s] and s not in self._held]
        if not free:
            self.last_admit_block = "no_slot"
            return None
        prompt = self._chain(prompt)
        Sg = self.cache_spec.slots_per_group
        first_free: Dict[int, int] = {}
        for s in free:
            g = s // Sg
            if exclude_groups and g in exclude_groups:
                continue
            first_free.setdefault(g, s)
        best = None
        best_key = None
        for g, s in first_free.items():
            if not self.allocator.can_admit(g, prompt,
                                            int(max_new_tokens),
                                            self.spec_k):
                continue
            key = (self.allocator.matched_blocks(g, prompt),
                   self.allocator.available(g))
            if best_key is None or key > best_key:
                best, best_key = s, key
        if best is not None:
            self._held.add(best)
        else:
            self.last_admit_block = "reservation"
        return best

    def _chain(self, prompt) -> kv_cache.PromptChain:
        """``prompt`` (bare, or the chain a ``Request`` keeps) as a chain
        walked at this engine's block size: where the engine's admission
        calls start.  A walk made here is the allocator's count and the
        aggregator's (``snapshot()["prefix"]["chain_walks"]``)."""
        chain = kv_cache.PromptChain.of(prompt)
        chain.hashes(self.block_size, by=(self.allocator, self.serving))
        return chain

    def last_admit_info(self, slot: int) -> Dict[str, Any]:
        """Prefix-cache/CoW detail of the most recent admission into
        ``slot`` (for the request trace)."""
        return self._last_admit.get(slot, {})

    def note_admission_reject(self, rid: Any, reason: str, attempt: int,
                              queue_depth: int = 0) -> None:
        """Count one admission rejection; the FIRST rejection of each
        request also writes a structured telemetry event (the retry loop
        used to be invisible in the stream)."""
        self.serving.note_reject()
        if attempt == 1 and self.telemetry.enabled:
            payload = {"rid": rid, "reason": reason,
                       "queue_depth": int(queue_depth)}
            if self.replica:
                payload["replica"] = self.replica
            self.telemetry.event("admission_rejected", payload)

    def prefix_match_tokens(self, prompt: Sequence[int]) -> int:
        """Longest cached prompt prefix (tokens) resident anywhere in
        this engine's block pool — the router's affinity signal. Host
        hash walk only; zero device work."""
        prompt = self._chain(prompt)
        return self.block_size * max(
            self.allocator.matched_blocks(g, prompt) for g in range(self.dp))

    # ------------------------------------------------------------------ #
    # The two serving operations
    # ------------------------------------------------------------------ #
    def prefill(self, prompt: Sequence[int], slot: int,
                temperature: float = 0.0, return_logits: bool = False,
                max_new_tokens: Optional[int] = None, rid: Any = None
                ) -> Tuple[int, Optional[np.ndarray]]:
        """Prefill one prompt into ``slot`` and sample its first output
        token. Returns (token, final-position logits [V] when asked —
        parity tests only; the serving loop needs just the token, and a
        per-admission [V] fetch would be a wasted host transfer). The
        caller activates the slot (scheduler owns admission ordering).

        The prompt is first admitted through the block allocator:
        cached full-block prefixes are shared by refcount (only the
        tail re-prefills — the TTFT win), an exactly-matched
        chain forks its final block copy-on-write before the first
        write, and ``max_new_tokens`` (the scheduler passes the
        request's) books the worst-case HBM reservation so mid-flight
        appends can never strand the slot. Direct calls without it
        reserve nothing and draw from the free pool lazily.

        ``rid`` only labels the ``prefill`` host span (see
        ``prefill_many``)."""
        return self.prefill_many(
            [(slot, prompt, int(max_new_tokens or 0))], temperature,
            return_logits=return_logits,
            rids=None if rid is None else [rid])[0]

    def prefill_many(self, admissions: Sequence[Tuple[int, Any, int]],
                     temperature: float = 0.0,
                     return_logits: bool = False,
                     rids: Optional[Sequence[Any]] = None
                     ) -> "list[Tuple[int, Optional[np.ndarray]]]":
        """Batched admission: prefill up to ONE slot per dp group in a
        single pass of group-batched chunk programs.

        ``admissions``: [(slot, prompt, max_new_tokens)] with every slot
        in a DISTINCT group — the scheduler gathers them that way. A
        lone admission leaves the other groups computing masked garbage
        (the uniform program); a full batch does real work in every
        group, which is what keeps saturation-time TTFT flat as dp
        grows: G admissions cost one admission's wall. Copy-on-write
        forks across the batch merge into ONE block-copy call (distinct
        groups can't collide). Returns [(first token, logits|None)] in
        admission order.

        Host spans: ``prefill`` (args ``slots``, ``prompt_tokens``,
        ``rids`` — the scheduler's request ids, so a request can be
        followed through a trace — and, once planned, ``cached_tokens``,
        ``chunks``, ``rows_computed``: the ``[G, width]`` rows of every
        chunk program, each as wide as it was dispatched; of a model with
        NAMED classes of cache layers also ``cached_tokens_<class>``,
        ``<class>_blocks_returned`` for a class with a reach — blocks its
        streams gave back WHILE these admissions' chunks were dispatched,
        the window sliding during prefill; on the ``decode`` span the same
        name is the running total — and ``context_tokens_in_reach_<class>``)
        > ``prefill_plan`` (allocator admission + the copy-on-write fork),
        one ``prefill_chunk`` (``ci``, ``active_groups``, ``rows``: the
        width, ``head``: 1 where the program ends some prompt and so runs
        the head and samples, else 0) per chunk program dispatched, and
        ``prefill_fetch`` (the first tokens' ``device_get``)."""
        if not self._prefill_warmed:
            self._warm_prefill_widths()
        t_pf0 = self.serving.lap("admit_s")
        tl = self.telemetry
        with tl.span("prefill", slots=len(admissions),
                     prompt_tokens=sum(len(p) for _, p, _ in admissions),
                     rids=ids_arg(rids)) as span:
            returned0 = self._bounded_returned()
            with tl.span("prefill_plan"):
                pools, plans, tails = self._plan_prefill(admissions)
            try:
                steps, held, widths = self._run_prefill_chunks(
                    pools, plans, tails, np.float32(temperature))
            except BaseException:
                for plan in plans:
                    self.allocator.abandon_snapshot(plan[2])
                raise
            tl.raise_pending()
            waited = self._await_decode()
            out = []
            n_ctr = len(self.served.counter_names)
            with tl.span("prefill_fetch"):
                # One fetch a chunk program that ended a prompt; the
                # model's counters (of that execution) ride it.
                fetched = {ci: split_counters(np.asarray(jax.device_get(
                    steps[ci][0])), n_ctr) for ci, _ in held.values()}
                self._note_counters(span, [c for _, c in fetched.values()])
                for slot, group, plan, prompt, plen in plans:
                    ci, g = held[slot]
                    tok = int(fetched[ci][0][g])
                    logits = np.asarray(jax.device_get(steps[ci][1][g])) \
                        if return_logits else None
                    if self.drafter is not None:
                        self.drafter.begin(slot, prompt)
                    self.serving.note_admit(plen, plan.matched)
                    out.append((tok, logits))
                if return_logits and self.served.probe_names:
                    rows = [jax.device_get(jax.tree.map(
                        lambda p, g=held[slot][1]: p[g],
                        steps[held[slot][0]][2])) for slot, *_ in plans]
                    self.last_probes = {
                        name: np.stack([np.asarray(r[i]) for r in rows])
                        for i, name in enumerate(self.served.probe_names)}
            cached = sum(int(p[2].matched) for p in plans)
            computed = self.dp * sum(widths)
            span.set_metadata(cached_tokens=cached, chunks=len(steps),
                              rows_computed=computed, chain_walks=sum(
                                  self._last_admit[p[0]]["chain_walks"]
                                  for p in plans))
            state = self.allocator.span_args(plans=[p[2] for p in plans])
            if state:
                span.set_metadata(**state)
                self.serving.note_state(state,
                                        self.allocator.snapshot_totals())
            by_class: Dict[str, int] = {}
            for p in plans:
                for name, n in (p[2].cached_by_class or {}).items():
                    by_class[name] = by_class.get(name, 0) + n
            span.set_metadata(**{"cached_tokens_" + name: n
                                 for name, n in by_class.items()})
            if returned0 is not None:
                returned = {name: n - returned0[name] for name, n
                            in self._bounded_returned().items()}
                span.set_metadata(
                    **{name + "_blocks_returned": n
                       for name, n in returned.items()},
                    **self._chunk_reach_args(tails))
                self.serving.note_admit_classes(returned, by_class)
        wall = self.serving.note_prefill_pass(
            len(steps), sum(p[4] for p in plans) - cached, computed,
            widths) - t_pf0 - waited
        self._prefill_wall += wall
        if self.serving.ledger is not None:
            self.serving.ledger.note("prefill", wall)
        return out

    def _bounded_returned(self) -> Optional[Dict[str, int]]:
        """{class: blocks its streams returned so far} over a model's
        NAMED classes that have a reach (None for a model without named
        classes: its ``prefill`` span gets no class args)."""
        stats = self.allocator.class_stats()
        if not stats:
            return None
        return {name: st["returned"] for name, st in stats.items()
                if st["reach"] is not None}

    def _chunk_reach_args(self, tails) -> Dict[str, int]:
        """The ``prefill`` span's ``context_tokens_in_reach_<class>``: key
        rows the chunk programs of these admissions may read, a class of
        pages at a time, summed over its layers — a chunk of ``n`` rows
        that starts at ``first`` reads ``first + n`` rows back at most, a
        bounded class no more than ``reach + n - 1`` of them."""
        args = {}
        for sp in self._attend_specs:
            rows = 0
            for chunks, _ in tails:
                for first, n in chunks:
                    seen = first + n
                    if sp.reach is not None:
                        seen = min(seen, sp.reach + n - 1)
                    rows += seen
            args["context_tokens_in_reach_" + sp.name] = \
                rows * sp.num_layers
        return args

    def _await_decode(self) -> float:
        """An admission's programs queue on the device behind the decode
        iteration in flight, and its token fetch would wait for both.
        Wait for THAT iteration first (ready on the device: its tokens
        stay there), so its wait is filed as the decode's (``fetch_s``)
        and what is left of the admission's fetch is the admission's
        own. Returns the seconds waited."""
        flight = self._inflight
        if flight is None:
            return 0.0
        t0 = self.serving.lap("prefill_s")
        jax.block_until_ready(flight.fetch)
        return self.serving.lap("fetch_s") - t0

    def _copy_blocks(self, pools, pairs):
        """Dispatch the copy program for ``{group: (src, dst)}`` block
        ids (-1: nothing to copy in the group)."""
        src = np.full(self.dp, -1, np.int32)
        dst = np.full(self.dp, -1, np.int32)
        for g, (s, d) in pairs.items():
            src[g], dst[g] = s, d
        self.serving.lap("prefill_s")
        pools = self._copy_fn(*pools, src, dst)
        self.serving.lap("copy_s")       # the host's part: the dispatch
        return pools

    def _plan_prefill(self, admissions):
        """prefill_many's ``prefill_plan``: admit every prompt through
        the block allocator, run the merged copy-on-write fork, and lay
        out each admission's unshared tail in chunks. Returns (pools,
        [(slot, group, plan, prompt, plen)], [([(first token, tokens) per
        chunk], index of the chunk that reaches the snapshot's boundary —
        with its last row, where the tail was cut there — or None)])."""
        G = self.dp
        J = self.allocator.table_width
        Sg = self.cache_spec.slots_per_group
        chunk = self.prefill_chunk
        pools = self._pools()
        plans = []
        seen_groups = set()
        forks = {}       # group -> (src, dst): copied before any chunk
        walks = {}
        for slot, prompt, max_new in admissions:
            chain = self._chain(prompt)
            prompt = chain.tokens
            plen = int(prompt.shape[0])
            if plen < 1:
                raise ValueError("empty prompt")
            if plen >= self.max_len:
                raise ValueError(
                    f"prompt length {plen} leaves no room to generate "
                    f"in a {self.max_len}-token slot")
            group = slot // Sg
            if group in seen_groups:
                raise ValueError(
                    f"prefill_many: two admissions in group {group} — "
                    "batch at most one slot per dp group")
            seen_groups.add(group)
            plan = self.allocator.admit_prompt(
                slot, group, chain, int(max_new), self.spec_k)
            walks[slot] = chain.walks
            row = np.full(J, kv_cache.DEAD_BLOCK, np.int32)
            row[:len(plan.table)] = plan.table
            self.block_tables[slot] = row
            if plan.cow_src is not None:
                forks[group] = (plan.cow_src, plan.cow_dst)
            plans.append((slot, group, plan, prompt, plen))
        if forks:
            pools = self._copy_blocks(pools, forks)
        # Chunk schedule: admission a runs chunks over its unshared
        # tail; all admissions advance together, groups whose tail is
        # done go inactive (writes land nowhere).  Where a snapshot is due
        # (``plan.snapshot_at``) the chunk program that passes its
        # boundary leaves it, if the model's can
        # (``ServedModel.freezes_in_chunk``); else the tail is cut there,
        # since such a state can only be frozen at the end of a program.
        tails = []
        for slot, group, plan, prompt, plen in plans:
            cuts = [plan.matched, plen]
            if not self._freeze_in_chunk \
                    and plan.matched < plan.snapshot_at < plen:
                cuts.insert(1, plan.snapshot_at)
            chunks = [(a, min(chunk, hi - a)) for lo, hi in
                      zip(cuts, cuts[1:]) for a in range(lo, hi, chunk)]
            snap_in = next((i for i, (a, n) in enumerate(chunks)
                            if a < plan.snapshot_at <= a + n), None)
            tails.append((chunks, snap_in))
            self._last_admit[slot] = {
                "cached_tokens": int(plan.matched), "chunks": len(chunks),
                "cow_fork": plan.cow_src is not None,
                "chain_walks": walks[slot]}
            if plan.cached_by_class:
                self._last_admit[slot]["cached_by_class"] = dict(
                    plan.cached_by_class)
            if plan.copy_class is not None:
                self._last_admit[slot].update(
                    snapshot_at=int(plan.snapshot_at),
                    lost_to_kind_tokens=int(plan.lost_to_kind))
        return pools, plans, tails

    def _run_prefill_chunks(self, pools, plans, tails, temp):
        """Dispatch one group-batched chunk program per chunk index (a
        ``prefill_chunk`` span each), as wide as the narrowest of
        ``prefill_widths`` that holds the longest active group's rows
        (only a tail's last chunk, or the one before a snapshot cut, is
        short), and store the cache. A snapshot due is left by the chunk
        program that reaches its boundary (``_freeze_in_chunk``: its row
        and page are the program's operands) or by a copy of the
        stream's page behind it. Returns ([(tok_g, logits_g) device
        arrays per chunk index], {slot: (ci, group) of its last chunk},
        [width per chunk index]). A dispatch that ends no group's prompt
        tells its program so (``read`` 0: no head, no sample, zeros in
        their place) and is never fetched; the ``prefill_chunk`` span's
        ``head`` says which a dispatch was."""
        G = self.dp
        J = self.allocator.table_width
        held = {}
        steps = []
        widths = []
        try:
            for ci in range(max(len(chunks) for chunks, _ in tails)):
                rows = max(chunks[ci][1] for chunks, _ in tails
                           if ci < len(chunks))
                width = next(w for w in self.prefill_widths if w >= rows)
                toks = np.zeros((G, width), np.int32)
                bt_rows = np.full((G, J), kv_cache.DEAD_BLOCK, np.int32)
                starts = np.zeros(G, np.int32)
                last_idxs = np.zeros(G, np.int32)
                act = np.zeros(G, np.int32)
                read = np.int32(0)           # does a group's prompt end here
                freeze = self._no_freeze()   # (row, page) [G], or ()
                snaps = {}   # group -> (own page, snapshot page): copied
                frozen = []  # plans whose snapshot this dispatch leaves
                for (slot, group, plan, prompt, plen), (chunks, snap_in) \
                        in zip(plans, tails):
                    if ci >= len(chunks):
                        continue
                    first, n = chunks[ci]
                    toks[group, :n] = prompt[first:first + n]
                    # (blocks are drawn lazily, program by program)
                    self.allocator.extend(slot, self.block_tables[slot],
                                          first, first + n - 1)
                    bt_rows[group] = self.block_tables[slot]
                    starts[group] = first
                    act[group] = 1
                    # The chunk's last row that belongs to the prompt (the
                    # prompt's last token in the last chunk). Rows past it
                    # are padding a served model may skip.
                    last_idxs[group] = n - 1
                    if ci == len(chunks) - 1:
                        held[slot] = (ci, group)
                        read = np.int32(1)
                    if ci == snap_in:
                        frozen.append(plan)
                        if freeze:
                            freeze[0][group] = plan.snapshot_at - first - 1
                            freeze[1][group] = plan.snapshot_page
                            plan.snapshot_in_program = True
                        else:
                            snaps[group] = (plan.page, plan.snapshot_page)
                with self.telemetry.span("prefill_chunk", ci=ci,
                                         active_groups=int(act.sum()),
                                         rows=width, head=int(read)):
                    pools, *step = self._outputs(self._prefill_fn(
                        self._params, *pools, toks, bt_rows, starts,
                        last_idxs, act, *freeze, read, self._next_key(),
                        temp))
                if snaps:
                    pools = self._copy_blocks(pools, snaps)
                # (dispatched: the device's order makes the page whole
                # before anything can resume from it)
                for plan in frozen:
                    self.allocator.commit_snapshot(plan)
                steps.append(tuple(step))
                widths.append(width)
        finally:
            # also where a chunk raised: the pools before it were donated
            self._store_pools(pools)
        return steps, held, widths

    def _no_freeze(self) -> Tuple[np.ndarray, ...]:
        """``prefill_step``'s operands for a snapshot left in the program,
        no group leaving one yet: (chunk row [G], page [G]) — or none at
        all for a model whose program takes none."""
        if not self._freeze_in_chunk:
            return ()
        return (np.zeros(self.dp, np.int32),
                np.full(self.dp, kv_cache.DEAD_BLOCK, np.int32))

    def _warm_prefill_widths(self) -> None:
        """Build ``prefill_step`` at every width of ``prefill_widths``
        before the first admission, so that traffic never compiles:
        each is dispatched once with every group inactive (dead rows and
        tables: nothing is written, attended or routed), widest first
        (``_WidthPrograms`` names the others' files by the first).
        Takes no key from the sampling sequence."""
        G = self.dp
        dead = np.full((G, self.allocator.table_width),
                       kv_cache.DEAD_BLOCK, np.int32)
        zeros = np.zeros(G, np.int32)
        pools = self._pools()
        try:
            for width in reversed(self.prefill_widths):
                pools = self._outputs(self._prefill_fn(
                    self._params, *pools, np.zeros((G, width), np.int32),
                    dead, zeros, zeros, zeros, *self._no_freeze(),
                    np.int32(0), self._base_rng, np.float32(0.0)))[0]
        finally:
            self._store_pools(pools)
        self._prefill_warmed = True
        self.telemetry.raise_pending()

    def _cache_accounting(self, mask: Optional[np.ndarray] = None
                          ) -> Tuple[int, int, int]:
        """(live blocks, cache bytes held, context tokens cached) this
        iteration — the hbm_bytes_per_token sample, and the ``decode``
        span's ``live_blocks`` / ``context_tokens`` (of the slots in
        ``mask``; every active one by default). Only live blocks are
        held."""
        tokens = int(self.lengths[self.active if mask is None
                                  else mask].sum())
        return (self.allocator.blocks_in_use(),
                self.allocator.bytes_in_use(), tokens)

    def _class_args(self, mask: np.ndarray) -> Dict[str, int]:
        """The ``decode`` span's args of a model with NAMED classes of
        cache layers (none otherwise): every class's blocks in use and
        blocks its streams returned so far, and the key rows the iteration
        may read (``context_tokens_in_reach``: over the slots in ``mask``,
        the classes of pages and their layers, a stream's context as far
        as the class reaches; a state holds no key rows); the classes'
        totals also go into ``snapshot()``."""
        stats = self.allocator.class_stats()
        if not stats:
            return {}
        self.serving.note_cache_classes(stats)
        args = {}
        for name, st in stats.items():
            args[name + "_blocks_live"] = st["live"]
            args[name + "_blocks_returned"] = st["returned"]
        lens = self.lengths[mask].astype(np.int64)
        args["context_tokens_in_reach"] = int(sum(
            sp.num_layers * (lens if sp.reach is None
                             else np.minimum(lens, sp.reach)).sum()
            for sp in self._attend_specs))
        return args

    def _attend_steps(self, k_rows: int,
                      lengths: Optional[np.ndarray] = None,
                      tables: Optional[np.ndarray] = None
                      ) -> Tuple[int, int, int]:
        """(steps, live steps, cold steps) a layer of the paged kernel's
        attend in the execution about to run — the ``decode`` span's
        ``attend_steps`` / ``attend_live_steps`` / ``attend_cold_steps``
        (see ``ops.paged_attention.attend_step_counts`` and
        ``attend_cold_steps``), from the lengths and tables the execution
        is handed (the host's own by default). Zeros on the one-hot path,
        which has no steps."""
        if not self.paged_kernel:
            return 0, 0, 0
        sp_ = self.cache_spec
        lengths = self.lengths if lengths is None else lengths
        tables = self.block_tables if tables is None else tables
        reach = (lengths + k_rows - 1) // sp_.block_size + 1
        live = np.minimum(
            np.minimum(reach, sp_.max_blocks_per_slot),
            (tables[:, :sp_.max_blocks_per_slot] >= 0).sum(axis=1))
        served = self.served
        return served.attend_step_counts(
            live, K=k_rows, spec=sp_, mp=self.mp, calls=self.dp,
            q_itemsize=int(jnp.dtype(served.dtype).itemsize))

    def _attend_cost(self, context: Optional[int] = None,
                     pool_blocks: Optional[int] = None,
                     spec=None) -> Tuple[int, int]:
        """Analytic (FLOPs, cache bytes) of ONE token's attend over all
        layers (of the class ``spec`` where given): live-context term
        (``context``: the stream's own blocks, the last one whole, as far
        as a class reaches) or pool-capacity term (``pool_blocks``: every
        row of a group's pool, what the one-hot contraction reads)."""
        if spec is None:
            costs = [self._attend_cost(
                context, pool_blocks and sp.blocks_per_group, sp)
                for sp in self._attend_specs]
            return sum(c[0] for c in costs), sum(c[1] for c in costs)
        if context is not None and spec.reach is not None:
            context = min(context, spec.reach)
        keys = paged_attn_ops._attend_keys(spec.block_size, context,
                                           pool_blocks)
        cost = self.__dict__.setdefault("_cache_costs", {})
        if (spec.name, keys) not in cost:
            flops, nbytes = self.served.cache_cost(
                keys, spec.block_size, int(jnp.dtype(spec.dtype).itemsize))
            cost[spec.name, keys] = (flops * spec.num_layers,
                                     nbytes * spec.num_layers)
        return cost[spec.name, keys]

    def _attend_work(self, k_rows: int, mask: Optional[np.ndarray] = None
                     ) -> Tuple[int, int, int, int]:
        """Analytic attend work of the iteration just run (over the
        slots in ``mask``; every active one by default), priced BOTH
        ways: (flops_kernel, flops_onehot, bytes_kernel, bytes_onehot).
        Kernel terms sum each live slot's ceil(ctx/bs)*bs keys (the K
        query rows share the block loads, so HBM bytes don't multiply
        by k_rows); one-hot terms are structural: every slot stream
        scores the whole pool and each dp group streams its full pool
        per layer, occupancy notwithstanding. Projections — host
        arithmetic, no device work. A served model's cost grows by the
        same amount with every block in reach (or not at all: a state),
        so the live slots' sum is one expression over their lengths, a
        class of layers at a time (a bounded class stops growing at its
        reach)."""
        bs = self.cache_spec.block_size
        blocks = -(-np.maximum(
            self.lengths[self.active if mask is None else mask], 1) // bs)
        n = int(blocks.size)
        out = np.zeros(4, np.int64)
        for sp_ in self._attend_specs:
            f1, b1 = self._attend_cost(context=bs, spec=sp_)
            f2, b2 = self._attend_cost(context=2 * bs, spec=sp_)
            reach = int((blocks if sp_.reach is None else np.minimum(
                blocks, -(-sp_.reach // bs))).sum())
            pool = self._attend_cost(pool_blocks=sp_.blocks_per_group,
                                     spec=sp_)
            out += ((n * (2 * f1 - f2) + reach * (f2 - f1)) * k_rows,
                    pool[0] * k_rows * self.max_slots,
                    n * (2 * b1 - b2) + reach * (b2 - b1),
                    pool[1] * sp_.num_groups)
        return tuple(int(v) for v in out)

    def _note_counters(self, span, counters) -> None:
        """The served model's counters of the execution(s) just fetched
        (they rode the token fetch): onto the host span and into the
        aggregator's running means. Nothing for a model without."""
        names = self.served.counter_names
        if not names or counters is None:
            return
        rows = np.asarray(counters, np.int64).reshape(-1, len(names))
        if not len(rows):
            return
        args = self.served.counter_args(rows)
        span.set_metadata(**args)
        self.serving.note_model_counters(args)

    def decode_once(self, temperature: float = 0.0,
                    return_logits: bool = False,
                    continuing: Optional[Sequence[int]] = None):
        """One pass of the decode loop: an iteration is one token for
        every slot in it (the other slots compute too — a uniform
        program is what keeps the signature fixed; their rows are dead
        and their counters do not advance).

        Called bare it is SYNCHRONOUS: it dispatches an iteration over
        every active slot, fetches it, and returns (the sampled token
        per slot, the [S, V] logits when asked — tests only; the extra
        fetch is not part of the serving loop).

        With ``continuing`` — the slots that take part in the iteration
        dispatched NOW: every live stream whose reply is not complete
        with the token it has in flight — it runs one iteration AHEAD
        of its token fetch: it dispatches that iteration (none for an
        empty set), fed by the tokens of the one in flight where they
        lie on the device (a slot activated since takes the host's),
        and only then fetches the one in flight. Returns (its tokens
        [S], the [S] mask of the slots that were in it and are still
        owed them), or (None, None) when nothing was in flight (the
        first call after a synchronous stretch: the next returns this
        one's). A slot released while a row of it is in flight (an EOS
        found one iteration late) is left out of that mask and counted
        as ``dropped``; that is the only work ever done in vain. The
        caller ends with a call whose set is empty, or with
        ``decode_discard``.

        The host's state moves in two steps: lengths, blocks and tables
        advance at the DISPATCH (they do not depend on the tokens);
        ``last_tokens``, the drafter, the model's counters and the
        iteration's sample on the timeline at the FETCH.

        Host spans: ``decode`` (``iteration``, ``active``,
        ``live_blocks``, ``context_tokens``, ``attend_*`` of the
        iteration dispatched; the model's counters of the one fetched;
        ``ahead``: 1 when the dispatch went out while the iteration
        before was unfetched; ``dropped`` rows of the one fetched) >
        ``decode_tables``, ``decode_dispatch`` (a dispatch),
        ``decode_fetch``, ``decode_advance`` (a fetch)."""
        ahead = continuing is not None
        if ahead and return_logits:
            raise ValueError("decode_once: logits are fetched by the "
                             "synchronous form only")
        prev = self._inflight
        if prev is not None and not ahead:
            raise RuntimeError(
                "decode_once: an iteration is in flight — fetch it "
                "(continuing=()) or decode_discard() it first")
        if ahead:
            mask = np.zeros(self.max_slots, bool)
            mask[np.fromiter(continuing, np.int64)] = True
            if (mask & ~self.active).any():
                raise ValueError("decode_once: continuing names a slot "
                                 "that is not active")
        else:
            mask = self.active.copy()
        t0 = self.serving.lap("other_s")     # the timeline's clock
        tl = self.telemetry
        tl.profiler_tick(self.iterations)
        n_active = int(mask.sum())
        dispatch = bool(n_active) or not ahead
        with tl.span("decode",
                     iteration=self.iterations + (dispatch and
                                                  prev is not None),
                     active=n_active) as span:
            flight = self._decode_dispatch(span, mask, n_active, prev,
                                           temperature, t0) \
                if dispatch else None
            self._inflight = flight if ahead else None
            due = prev if ahead else flight
            sampled = took = None
            if due is not None:
                sampled = self._decode_fetch(span, due)
                took = due.mask
            span.set_metadata(
                ahead=flight.ahead if flight is not None else 0,
                dropped=due.dropped if due is not None else 0)
        if ahead:
            return sampled, took
        out_logits = None
        if return_logits:
            out_logits = np.asarray(jax.device_get(due.logits))
            if due.probes is not None:
                self.last_probes = dict(zip(
                    self.served.probe_names,
                    map(np.asarray, jax.device_get(due.probes))))
        return sampled, out_logits

    def _decode_dispatch(self, span, mask: np.ndarray, n_active: int,
                         prev: Optional[_Flight], temperature: float,
                         t0: float) -> _Flight:
        """Dispatch one iteration over the slots in ``mask`` and advance
        the host's lengths and tables by it."""
        tl, lap = self.telemetry, self.serving.lap
        with tl.span("decode_tables"):
            for s in np.flatnonzero(mask):
                at = int(self.lengths[s])
                self.allocator.extend(int(s), self.block_tables[s], at, at)
            # What the execution is handed: COPIES (the host's arrays
            # move on under it), dead rows for the slots not in it, and
            # the host's token for a slot the execution before did not
            # decode (all of them when none is unfetched).
            lengths = np.where(mask, self.lengths, np.int32(0))
            tables = np.where(mask[:, None], self.block_tables,
                              np.int32(kv_cache.DEAD_BLOCK))
            fresh = np.ones_like(mask) if prev is None \
                else self._fresh | ~prev.mask
            steps = self._attend_steps(1, lengths, tables)
        lap("tables_s")
        with tl.span("decode_dispatch"):
            pools, fetch, logits, probes = self._outputs(self._decode_fn(
                self._params, *self._pools(),
                self._no_fetch if prev is None else prev.fetch,
                self.last_tokens.copy(), fresh, lengths, tables,
                self._next_key(), np.float32(temperature)))
            self._store_pools(pools)
            tl.raise_pending()
            self._fresh[:] = False
            self.lengths[mask] += 1
            live_blocks, cache_bytes, ctx_tokens = \
                self._cache_accounting(mask)
            self.serving.note_attend_steps(*steps)
            state = self.allocator.span_args(live=n_active)
            if state:       # pages a stream: did their filter rows go
                state["filter_rows_in_place"] = self.filter_rows_in_place
            if n_active:
                self.serving.note_attend(*self._attend_work(1, mask),
                                         n_active)
            span.set_metadata(live_blocks=live_blocks,
                              context_tokens=ctx_tokens,
                              attend_steps=steps[0],
                              attend_live_steps=steps[1],
                              attend_cold_steps=steps[2],
                              **self._class_args(mask), **state)
        lap("dispatch_s")
        return _Flight(fetch=fetch, logits=logits, probes=probes, mask=mask,
                       n_active=n_active, t0=t0,
                       prefill_s=self._prefill_wall,
                       ahead=int(prev is not None),
                       cache_bytes=cache_bytes, context_tokens=ctx_tokens)

    def _decode_fetch(self, span, flight: _Flight) -> np.ndarray:
        """Fetch an iteration's tokens — the serving loop's one sync, the
        served model's counters riding it — and do what waited for
        them."""
        tl, lap = self.telemetry, self.serving.lap
        with tl.span("decode_fetch"):
            sampled, counters = split_counters(
                np.asarray(jax.device_get(flight.fetch)),
                len(self.served.counter_names))
        lap("fetch_s")
        with tl.span("decode_advance"):
            adv = flight.mask
            self.last_tokens[adv] = sampled[adv]
            if self.drafter is not None:
                for s in np.flatnonzero(adv):
                    self.drafter.observe(int(s), [int(sampled[s])])
            # From the later of this iteration's dispatch and the tokens
            # of the one before, less the admissions between.
            end = lap("advance_s")
            start, prefill_s = max((flight.t0, flight.prefill_s),
                                   self._t_tokens)
            wall = end - start - (self._prefill_wall - prefill_s)
            self._t_tokens = (end, self._prefill_wall)
            self.iterations += 1
            n_active, tokens = flight.n_active, int(adv.sum())
            self.serving.note_iteration(
                n_active, wall, cache_bytes=flight.cache_bytes,
                context_tokens=flight.context_tokens,
                emitted_tokens=tokens, ahead=flight.ahead,
                dropped=flight.dropped)
            if self.serving.ledger is not None:
                self.serving.ledger.note("decode_useful", wall)
            if tl.enabled:
                tl.record_step(
                    self.iterations, {}, wall_ms=wall * 1e3,
                    active_slots=n_active,
                    occupancy=round(n_active / self.max_slots, 4),
                    tokens=tokens)
                tl.maybe_drain(self.iterations,
                               extra_fn=self._report_extra)
        self._note_counters(span, counters)
        return sampled

    def decode_discard(self) -> None:
        """Forget the iteration in flight without fetching it (a serve
        cut short). The lengths of the slots it held moved with its
        dispatch and their tokens are lost: the caller releases every
        one of them."""
        self._inflight = None

    def spec_decode_once(self, temperature: float = 0.0
                         ) -> Tuple[np.ndarray, np.ndarray]:
        """One speculative draft-then-verify iteration for every slot.

        The n-gram drafter proposes ``spec_k`` tokens per live slot
        (host-side, free), ONE batched verify step scores
        [last, d_1..d_k] through the paged cache, and the in-graph
        acceptance rule emits the longest agreeing prefix plus the
        correction/bonus token — 1..k+1 tokens per slot per iteration,
        greedy-bit-identical to plain decode. Still exactly one host
        fetch. Returns (emitted [S, k+1] int32, n_new [S] — how many
        leading emitted tokens are real per slot; 0 for inactive)."""
        if not self.spec_enabled:
            raise RuntimeError("spec_decode_once needs inference.spec_k "
                               "> 0")
        if float(temperature) > 0.0:
            raise ValueError(
                "spec_decode_once is greedy-only (the acceptance rule "
                "has no rejection-sampling correction); use "
                "decode_once for temperature > 0 — the scheduler falls "
                "back automatically")
        if self._inflight is not None:
            # The accepted count decides the lengths: nothing here can
            # be dispatched ahead of its fetch.
            raise RuntimeError("spec_decode_once: a decode iteration is "
                               "in flight")
        lap = self.serving.lap       # the timeline's clock
        t0 = lap("other_s")
        tl = self.telemetry
        tl.profiler_tick(self.iterations)
        k = self.spec_k
        n_active = self.active_slots
        with tl.span("decode", iteration=self.iterations,
                     active=n_active) as span:
            with tl.span("decode_tables"):
                toks = np.zeros((self.max_slots, k + 1), np.int32)
                toks[:, 0] = self.last_tokens
                live = np.flatnonzero(self.active)
                for s in live:
                    s = int(s)
                    toks[s, 1:] = self.drafter.propose(s)
                    self.allocator.extend(
                        s, self.block_tables[s], int(self.lengths[s]),
                        min(int(self.lengths[s]) + k, self.max_len - 1))
                steps = self._attend_steps(k + 1)
            lap("tables_s")
            with tl.span("decode_dispatch"):
                *pools, out, logits = self._verify_fn(
                    self._params, *self._pools(), toks,
                    self.lengths, self.block_tables, self._next_key(),
                    np.float32(temperature))
                self._store_pools(pools)
                tl.raise_pending()
            lap("dispatch_s")
            with tl.span("decode_fetch"):
                out = np.asarray(jax.device_get(out))    # [S, k+2]
            lap("fetch_s")
            with tl.span("decode_advance"):
                n_new = out[:, 0].copy()
                emitted = out[:, 1:]
                n_new[~self.active] = 0
                accepted = 0
                for s in live:
                    s = int(s)
                    n = max(0, min(int(n_new[s]),
                                   self.max_len - int(self.lengths[s])))
                    n_new[s] = n
                    if n == 0:
                        continue
                    self.lengths[s] += n
                    self.last_tokens[s] = int(emitted[s, n - 1])
                    self.drafter.observe(s, emitted[s, :n])
                    accepted += n - 1
                emitted_total = int(n_new.sum())
                self._spec_proposed += k * len(live)
                self._spec_accepted += accepted
                wall = lap("advance_s") - t0
                self.iterations += 1
                live_blocks, cache_bytes, ctx_tokens = \
                    self._cache_accounting()
                self.serving.note_attend_steps(*steps)
                if n_active and emitted_total:
                    self.serving.note_attend(*self._attend_work(k + 1),
                                             emitted_total)
                self.serving.note_iteration(n_active, wall,
                                            cache_bytes=cache_bytes,
                                            context_tokens=ctx_tokens,
                                            emitted_tokens=emitted_total)
                if self.serving.ledger is not None:
                    # Split the verify wall by row share: of the (k+1)
                    # verify rows per live slot, the emitted tokens
                    # (accepted drafts + the correction/bonus) are useful
                    # work; the rejected drafts are wall the draft caused
                    # and the target threw away.
                    rows = (k + 1) * len(live)
                    wasted = wall * (k * len(live) - accepted) / rows \
                        if rows else 0.0
                    self.serving.ledger.note("spec_wasted", wasted)
                    self.serving.ledger.note("decode_useful",
                                             wall - wasted)
                self.serving.note_spec(k * len(live), accepted)
                if tl.enabled:
                    tl.record_step(
                        self.iterations, {}, wall_ms=wall * 1e3,
                        active_slots=n_active,
                        occupancy=round(n_active / self.max_slots, 4),
                        tokens=emitted_total, spec_accepted=accepted)
                    tl.maybe_drain(self.iterations,
                                   extra_fn=self._report_extra)
            span.set_metadata(live_blocks=live_blocks,
                              context_tokens=ctx_tokens,
                              attend_steps=steps[0],
                              attend_live_steps=steps[1],
                              attend_cold_steps=steps[2])
        return emitted, n_new

    def _attach_slo_overlays(self) -> None:
        """Attach the serving goodput ledger (always — host arithmetic)
        and, when ``inference.slo`` sets a target, the SLO tracker."""
        self.serving.ledger = ServingGoodputLedger(
            label=self.replica or None)
        scfg = self.icfg.slo
        if scfg.enabled:
            self.serving.slo = SLOTracker(
                ttft_ms=scfg.ttft_ms, tpot_ms=scfg.tpot_ms,
                availability=scfg.availability, window_s=scfg.window_s)

    def reset_serving_stats(self) -> None:
        """Fresh aggregator window (benches call this after a warmup
        pass so compile time never pollutes the measured TTFT/TPOT
        stream — both sides of a comparison warm the same way)."""
        self.serving = ServingAggregator(self.max_slots,
                                         label=self.replica or None,
                                         clock=self.serving.clock)
        self.serving.attend_mode = ("kernel" if self.paged_kernel
                                    else "onehot")
        self._attach_slo_overlays()
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._t_tokens = (float("-inf"), 0.0)   # the clock may be another

    def _report_extra(self) -> Dict[str, Any]:
        return {"serving": self.serving.snapshot()}

    def profile_window(self, steps: int,
                       start_step: Optional[int] = None) -> Optional[str]:
        """Arm a ``jax.profiler`` capture over ``steps`` decode
        iterations (default: starting at the next iteration). The trace
        is ingested and reconciled at the next telemetry drain
        (``telemetry.profile`` block); with telemetry off this is a
        no-op returning None. Zero device syncs are added when no window
        is armed — the PR-4 fence contract."""
        return self.telemetry.arm_profile_window(
            int(steps), start_step=self.iterations + 1
            if start_step is None else int(start_step))

    def complete_request(self, rid: Any, ttft_s: float,
                         tpot_s: Optional[float], prompt_tokens: int,
                         new_tokens: int,
                         queue_wait_s: Optional[float] = None,
                         service_ttft_s: Optional[float] = None,
                         admission_attempts: Optional[int] = None) -> None:
        """Per-request goodput accounting at completion (host clocks
        only): feeds the aggregator and SLO tracker and writes a
        ``request_complete`` telemetry event. ``queue_wait_s`` /
        ``service_ttft_s`` split the TTFT at the admission instant."""
        self.serving.note_request(ttft_s, tpot_s, new_tokens,
                                  queue_wait_s=queue_wait_s,
                                  service_ttft_s=service_ttft_s,
                                  admission_attempts=admission_attempts)
        if self.serving.slo is not None:
            self.serving.slo.observe(ttft_s, tpot_s)
        if self.telemetry.enabled:
            payload = {"rid": rid, "ttft_ms": round(ttft_s * 1e3, 3),
                       "prompt_tokens": int(prompt_tokens),
                       "new_tokens": int(new_tokens)}
            if tpot_s is not None:
                payload["tpot_ms"] = round(tpot_s * 1e3, 3)
            if queue_wait_s is not None:
                payload["queue_wait_ms"] = round(queue_wait_s * 1e3, 3)
            if service_ttft_s is not None:
                payload["service_ttft_ms"] = round(service_ttft_s * 1e3, 3)
            if admission_attempts:
                payload["admission_attempts"] = int(admission_attempts)
            if self.replica:
                payload["replica"] = self.replica
            self.telemetry.event("request_complete", payload)

    def abort_request(self, rid: Any, reason: str = "abort") -> None:
        """An aborted/evicted request: counts against SLO availability
        and leaves a structured event in the stream."""
        if self.serving.slo is not None:
            self.serving.slo.observe_failure()
        if self.telemetry.enabled:
            payload = {"rid": rid, "reason": reason}
            if self.replica:
                payload["replica"] = self.replica
            self.telemetry.event("request_abort", payload)

    def serve(self, requests, temperature: float = 0.0, **kwargs):
        """Drive a request list/stream through the continuous-batching
        scheduler; see inference/scheduler.py."""
        from .scheduler import ContinuousBatchingScheduler
        sched = ContinuousBatchingScheduler(self, temperature=temperature,
                                            **kwargs)
        return sched.serve(requests)

    # ------------------------------------------------------------------ #
    # Training-checkpoint handoff
    # ------------------------------------------------------------------ #
    @classmethod
    def from_train_checkpoint(cls, load_dir: str, model_cfg: Any,
                              config: Any = None, tag: Optional[str] = None,
                              mesh: Optional[Mesh] = None,
                              rng: Optional[jax.Array] = None,
                              init_fn: Optional[Callable] = None
                              ) -> "InferenceEngine":
        """Build a serving engine from a training engine's checkpoint
        directory (the ``latest``-pointer + ``mp_rank_00`` layout
        runtime/engine.py saves). ``init_fn(rng, cfg) -> params``
        defaults to the served model's own (``models.gpt2.gpt2_init``
        for GPT-2's config) and is only used for its tree STRUCTURE
        (eval_shape — no real init runs)."""
        if flax_serialization is None:
            raise RuntimeError("flax is required to read checkpoints")
        if tag is None:
            latest = os.path.join(load_dir, "latest")
            if not os.path.isfile(latest):
                raise FileNotFoundError(f"no 'latest' pointer in {load_dir}")
            with open(latest) as f:
                tag = f.read().strip()
        path = os.path.join(load_dir, str(tag))
        model_file = os.path.join(path, "mp_rank_00_model_states.msgpack")
        if not os.path.isfile(model_file):
            raise NotImplementedError(
                f"{model_file} not found — TP-sharded (mp_rank_XX) "
                "checkpoints need assembly, load them through the "
                "training engine and pass raw params instead")
        served = served_model(model_cfg)
        if init_fn is None:
            init_fn = served.init_fn
        template = jax.tree_util.tree_map(
            lambda s: np.zeros(s.shape, s.dtype),
            jax.eval_shape(lambda r: init_fn(r, served.cfg),
                           jax.random.PRNGKey(0)))
        with open(model_file, "rb") as f:
            blob = flax_serialization.from_bytes({"module": template},
                                                 f.read())
        log_dist(f"serving from training checkpoint {path}", ranks=[0])
        return cls(model_cfg, blob["module"], config=config, mesh=mesh,
                   rng=rng)

    # ------------------------------------------------------------------ #
    # Static lint audit (analysis/) — duck-typed lint_engine contract
    # ------------------------------------------------------------------ #
    def _lint_path_meta(self, name: str) -> Dict[str, Any]:
        """Pass metadata for the serving paths: no gradient sync exists
        here, so collective_placement is inert; materialization scales
        from the PER-DEVICE params+cache footprint (matching the
        post-partitioning shapes in the compiled HLO), with the largest
        per-device leaf exempt as usual.

        ``paged_score_bytes`` declares the one-hot contraction's known
        fp32 score transient ([G, Q, K, nH, B, bs] per layer — it
        scales with pool CAPACITY, so a grown pool under a fixed param
        footprint would otherwise trip the fraction-of-declared
        watermark with no code change). Declaring it keeps the budget
        exact: the audit headroom covers exactly that transient, and
        anything bigger — a real full-pool K/V gather carries the extra
        head_dim factor — still fires. With the Pallas kernel on the
        transient does not exist, no budget is declared, and a clean
        materialization pass IS the proof the kernel path materializes
        nothing pool-sized."""
        state = {"params": self._params, "cache": self.cache}
        per_dev_leaves = []
        for leaf in jax.tree_util.tree_leaves(state):
            shape = getattr(leaf, "shape", None)
            if shape is None:
                continue
            sharding = getattr(leaf, "sharding", None)
            if sharding is not None and hasattr(sharding, "shard_shape"):
                try:
                    shape = sharding.shard_shape(tuple(shape))
                except Exception:
                    pass
            per_dev_leaves.append(
                int(np.prod(shape)) * jnp.dtype(leaf.dtype).itemsize)
        score_bytes = 0
        if not self.paged_kernel:
            sp_ = self.cache_spec
            q_streams = {"decode_step": (sp_.slots_per_group, 1),
                         "verify_step": (sp_.slots_per_group,
                                         self.spec_k + 1),
                         # (the widest of ``prefill_widths``; the
                         # registry holds the last that compiled, the
                         # narrowest, which this covers)
                         "prefill_step": (1, self.prefill_chunk)}
            q_, k_ = q_streams.get(name, (0, 0))
            if q_ and k_:
                nh_loc = max(1, sp_.num_heads // self.mp)
                pool_keys = sp_.blocks_per_group * sp_.block_size
                score_bytes = max(
                    q_ * k_ * nh_loc * pool_keys * 4,       # s_all / wb
                    q_ * sp_.max_blocks_per_slot
                    * sp_.blocks_per_group * 4)             # selector
        return {
            "grad_sync_path": False,
            "grad_sync_mode": "none",
            "gas": 1,
            "scatterable_leaf_bytes": [],
            "declared_state_bytes": int(analytic_state_bytes(state)),
            "param_bytes_full": int(self.param_bytes),
            "largest_leaf_bytes": max(per_dev_leaves, default=0),
            "paged_score_bytes": int(score_bytes),
            "dp": self.dp,
            "zero_stage": 0,
        }

    def lint_audit(self, config=None, waivers=None, passes=None):
        """Compile-time lint over the decode/prefill paths (host-side
        AOT re-lower from the sentinel registry; zero device fences).
        The serving contract: host_sync and materialization clean — no
        full-cache gather, no in-step host transfer."""
        from ..analysis.auditor import lint_engine
        return lint_engine(self, config=config, waivers=waivers,
                           passes=passes)

    def close(self) -> None:
        self.telemetry.close()


__all__ = ["InferenceEngine"]
