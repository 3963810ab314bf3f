"""Read a ``jax.profiler`` capture's ``.xplane.pb`` with the named-scope
path of every device operation.

A TPU capture has one plane per chip (``/device:TPU:<n>``) whose line
``XLA Ops`` holds one event per executed HLO instruction. The event
carries times only; its event METADATA carries the whole HLO line as its
name (the instruction's name is what stands before `` = ``) and the stat
``tf_op``: the instruction's ``op_name`` — the ``jax.named_scope`` path
the program's steps carry (``SCOPES``; docs/tutorials/telemetry.md) —
plus a ``:``. ``jax.profiler.ProfileData`` hands out an event's own
stats and not its metadata's, so the scope path is only reachable by
reading the protobuf: a small wire-format reader (field numbers from
tsl/profiler/protobuf/xplane.proto), no dependency beyond the standard
library. Host planes hold the ``Telemetry.span`` annotations by name,
their args as event stats.

Pure host-side parsing; no jax import.
"""
from __future__ import annotations

import re
import struct
from typing import Any, Dict, Iterator, List, Tuple

__all__ = ["SCOPES", "SPANS", "SPAN_ARGS", "CLASS_SPAN_ARGS", "span_args",
           "read_xspace", "event_args", "scope_of",
           "instruction_name", "idle_gaps", "named_idle_gaps",
           "device_op_events", "DEVICE_PLANE", "OPS_LINE"]

# The program's named scopes — a stable interface (the benchmark's
# readers and operators' dashboards key on them).
SCOPES = ("fwd_bwd", "grad_sync", "health_tap", "optimizer", "flatten",
          "norm", "kernel", "unflatten", "embed", "attn", "mlp", "lm_head",
          "kv_write", "attend", "sample", "cow_copy",
          # the residual-dropout masks (models/transformer.dropout), under
          # fwd_bwd > attn / mlp
          "dropout",
          # the latent-attention family (inference/latent.py): the
          # projections round the attend, and the expert layer's stages
          "latent_proj", "moe", "router", "dispatch", "experts", "combine",
          "shared",
          # the retention family (inference/retention.py): under attn, the
          # projections, the decode kernel over the state pool / the
          # chunked form in prefill, the output projection; and the page
          # copy of a per-stream pool (a program of its own)
          "qkv_proj", "state_update", "retention_chunk", "out_proj",
          "state_copy",
          # a model with two classes of cache layers (inference/afmoe.py):
          # under attn, the paged attend of a sliding-window layer and of a
          # full-attention layer (beside qkv_proj, kv_write, out_proj)
          "attend_window", "attend_full",
          # several residual streams (models/hyper_connections.py, the
          # latent family with ``hc_mult``): under attn / mlp / moe, hc >
          # hc_maps (the norm, the maps' product, sigmoids, Sinkhorn),
          # hc_pre (the sublayer's input mixed from the streams), hc_post
          # (its output written back); embed > hc_expand, lm_head >
          # hc_collapse
          "hc", "hc_maps", "hc_pre", "hc_post", "hc_expand", "hc_collapse",
          # gated short-convolution layers (inference/lfm2.py): conv >
          # conv_in_proj (norm, the gates' projection), conv_mix (the
          # gates' product, the filter, the rewrite of the stream's page),
          # conv_out_proj
          "conv", "conv_in_proj", "conv_mix", "conv_out_proj",
          # a state-space mixer beside attention in one layer
          # (inference/falcon_h1.py): ssm > ssm_in_proj, ssm_conv (the
          # filter rows' read, filter and rewrite; dt and the decay),
          # ssm_state_update (decode: the kernel over the state pool) /
          # ssm_chunk_scan (prefill), ssm_gate_norm, ssm_out_proj
          "ssm", "ssm_in_proj", "ssm_conv", "ssm_state_update",
          "ssm_chunk_scan", "ssm_gate_norm", "ssm_out_proj",
          # Kimi Delta Attention layers (inference/kimi_linear.py): under
          # attn, kda_proj (norm, the fused q/k/v projection), kda_conv (the
          # filter rows' read, the three filters, the L2 norms, the rewrite),
          # kda_gate (the decay a channel and the write strength),
          # kda_update (decode: the delta-rule kernel over the state pool) /
          # kda_chunk (prefill: the chunked form), kda_out (the gated head
          # norm and the output projection)
          "kda_proj", "kda_conv", "kda_gate", "kda_update", "kda_chunk",
          "kda_out",
          # ... beside grouped-query layers under an output gate
          # (inference/solar_open2.py): attn_gate, between the attend and
          # out_proj (the gate's sigmoid and its product with the attended
          # rows)
          "attn_gate",
          # sparse layers beside Lightning layers (inference/minicpm_sala
          # .py), all under attn: ck_write (the pooled keys whose windows
          # end at the new rows), select (their scores, softmax, sums,
          # maxima, top-k: pool block ids a row and K/V head),
          # attend_sparse (the per-K/V-head plan and the attend over the
          # chosen blocks) beside qkv_proj, kv_write, out_proj; la_proj
          # (q/k norm, rotary), la_state_update (decode: the state kernel,
          # several one-head groups a step) / la_chunk (prefill: the
          # chunked form), la_gate_norm, la_out
          "ck_write", "select", "attend_sparse", "la_proj",
          "la_state_update", "la_chunk", "la_gate_norm", "la_out",
          # a model generated by diffusion over blocks (inference/sdar.py):
          # in decode_step, behind lm_head: the proposals over the
          # vocabulary (> sample), their confidences and the rule's choice
          # of the positions a pass unmasks, for every slot's block
          "unmask")
# The host spans ``Telemetry.span`` opens (runtime/engine.py,
# inference/engine.py, inference/scheduler.py), same contract.
SPANS = ("train_batch", "data_prep", "step_dispatch", "offload_step",
         "step_log", "admit", "prefill", "prefill_plan", "prefill_chunk",
         "prefill_fetch", "decode", "decode_tables", "decode_dispatch",
         "decode_fetch", "decode_advance", "emit", "serve_idle",
         # start-up (monitor/startup.py: each also a row of the start-up
         # ledger): an engine's constructor and its children, the build
         # of the prefill widths, a kept executable's load; and the
         # instant marker a ``program_build`` row leaves when it closes
         # inside a profiler session
         "engine_init", "place_params", "allocate_cache", "shard_state",
         "warm_prefill_widths", "executable_load", "program_build")
# The args those spans carry (the ones with none are left out).
SPAN_ARGS = {
    # The call's row of the training timeline (monitor/training.py): the
    # interval since the entry before and the part of it since that call
    # returned; this call's three child spans and their sum, host_ms;
    # earlier steps not yet seen complete at the dispatch, steps first
    # seen complete since the entry before, step programs the call built.
    "train_batch": ("step_num", "row", "gap_ms", "outside_ms", "host_ms",
                    "data_ms", "dispatch_ms", "log_ms", "in_flight",
                    "completed", "built"),
    "data_prep": ("step",),
    "step_dispatch": ("step",), "step_log": ("step",),
    "admit": ("queued", "late_ms", "admitted", "rejected", "rids"),
    # moe_*: the served model's counters where it has an expert layer
    # (inference/latent.py): routed pairs that landed on held experts in
    # the execution(s) the span fetched, the largest and the mean rows a
    # held expert got in a layer, held experts (x layers) that got none,
    # the pairs' share of all the live rows routed. They ride the token
    # fetch. Absent for a model without counters (GPT-2).
    # hc_res_err_max: a model with several residual streams: the largest
    # deviation of a row or column sum of H_res from 1 over the live rows
    # of the execution(s) fetched (what the Sinkhorn iterations left).
    # resumed_tokens / snapshot_taken / snapshot_in_program /
    # state_copy_bytes: a per-stream state pool's admission
    # (inference/kv_cache.py): prompt tokens the snapshot it resumed from
    # covers (what cached_tokens means there), snapshots the admission
    # left, those of them the chunk program that reached the boundary
    # froze itself (a model that can: no cut, no copy), bytes the page
    # copies it did dispatch read + wrote.
    # prefix_lost_to_kind_tokens: a model that keeps pages BESIDE a state
    # a stream: prompt tokens its page classes had cached beyond the
    # boundary the state class had a snapshot at (prefilled again).
    # rows_computed: the [G, width] rows of every chunk program the
    # admission dispatched, each at the width it took (the narrowest of
    # the engine's prefill_widths that held its rows: prefill_chunk's
    # "rows"); prompt_tokens - cached_tokens of them were needed.
    # chain_walks: whole-prompt hash walks made for the span's requests
    # since each was queued (kv_cache.PromptChain.walks, summed): 1 a
    # request that came through the scheduler, however many passes asked
    # whether it could go in.
    # rows: a model whose every layer routes (inference/smallthinker.py):
    # the live rows the fetched execution(s) routed (a decode iteration's
    # live streams; on a prefill span the rows that are traffic in the
    # chunk program that ENDED the prompt, whose fetch the counters ride:
    # an earlier chunk's rows are all live).
    "prefill": ("slots", "prompt_tokens", "rids", "cached_tokens",
                "chunks", "rows_computed", "chain_walks",
                "moe_held_pairs", "moe_held_max",
                "moe_held_mean", "moe_held_empty", "moe_held_pair_share",
                "rows", "hc_res_err_max",
                # a model whose sparse layers select what they read
                # (inference/minicpm_sala.py), of the execution(s) fetched
                # (on a prefill span: the chunk program that ENDED the
                # prompt): blocks the attend walked and blocks a dense
                # attend would, each per live row, sparse layer and K/V
                # head; their ratio; pooled rows the selection scored and
                # blocks of pooled keys it gathered to score them
                "sparse_blocks_read", "sparse_blocks_in_reach",
                "sparse_read_share", "ck_rows_scored", "ck_blocks_read",
                "resumed_tokens", "snapshot_taken", "snapshot_in_program",
                "state_copy_bytes",
                "prefix_lost_to_kind_tokens"),
    # head: 1 where the chunk program ENDS some group's prompt and so runs
    # the model's head and the sampler, 0 where it skips both (its tokens
    # and logits are zeros nobody fetches).
    # write_rows / write_runs: the rows one layer's K/V write of the chunk
    # program lands in the first class's pages, and the runs (a stream's
    # rows in one page: a live grid step of the write) it lands them in
    # (ops.paged_attention.write_step_counts; absent for a model that
    # keeps no K/V pages)
    "prefill_chunk": ("ci", "active_groups", "rows", "head", "write_rows",
                      "write_runs"),
    # A decode span holds the DISPATCH of one iteration and the FETCH of
    # the one before (the loop runs an iteration ahead of its token
    # fetch): iteration .. attend_* and state_pages_live describe the one
    # dispatched (absent where the span dispatched none), the moe_*
    # counters the one fetched, each iteration once.
    # attend_steps / attend_live_steps / attend_cold_steps: the paged
    # kernel's sequencing steps a layer in this execution, those that
    # touch a live block, and the live ones whose first copies the step
    # before had not started (ops.paged_attention.attend_step_counts,
    # attend_cold_steps; zeros on the one-hot path)
    "decode": ("iteration", "active", "live_blocks", "context_tokens",
               "attend_steps", "attend_live_steps", "attend_cold_steps",
               "moe_held_pairs", "moe_held_max", "moe_held_mean",
               "moe_held_empty", "moe_held_pair_share", "rows",
               "hc_res_err_max",
               # (see "prefill": the iteration FETCHED)
               "sparse_blocks_read", "sparse_blocks_in_reach",
               "sparse_read_share", "ck_rows_scored", "ck_blocks_read",
               # pages of a per-stream pool the state-update kernel
               # rewrote in this execution (one a live stream)
               "state_pages_live",
               # beside it: 1 where the decode program rewrites those
               # streams' short-filter rows in place by a kernel
               # (ops/filter_rows.py), 0 where it gathers and scatters
               # them (inference/served.filter_rows picks by shape)
               "filter_rows_in_place",
               # 1 where the dispatch went out while the iteration
               # before was still unfetched; rows the iteration fetched
               # computed for streams that had ended by then
               "ahead", "dropped",
               # a model with NAMED classes of cache layers
               # (inference/kv_cache.py): the key rows the iteration may
               # read, summed over streams, classes and layers (a class
               # counts a stream's context as far as it reaches)
               "context_tokens_in_reach",
               # a model generated in blocks (inference/sdar.py), of the
               # pass FETCHED: rows it computed for live streams, slots
               # whose block it committed, positions it unmasked
               "block_rows", "commits", "unmasked",
               # ... and of the pass DISPATCHED, as ``prefill_chunk``'s:
               # rows a run is the block's length where a block lies in a
               # page
               "write_rows", "write_runs"),
    # The emission's row of the serving timeline (monitor/serving.py),
    # the streams it hands tokens to and those of them that waited the
    # whole interval since the emission before, that interval, the part
    # of it in other requests' prefill and copies, and the part on the
    # host outside decode_dispatch + decode_fetch.
    # A model generated in blocks: ``streams`` are those handed a BLOCK
    # in this row (``blocks``), and ``block_gaps_ms`` the time since each
    # one's block before, in ms, joined by spaces.
    "emit": ("finished", "row", "streams", "continuing", "gap_ms",
             "stall_ms", "host_ms", "blocks", "block_gaps_ms"),
    "serve_idle": ("why",),
    # age_s: the span's start in seconds of PROCESS AGE, the start-up
    # ledger's clock (``named_idle_gaps`` ties the two clocks by it).
    "engine_init": ("age_s", "mode", "param_bytes", "cache_bytes"),
    "place_params": ("age_s", "parent"),
    "allocate_cache": ("age_s", "parent"),
    "shard_state": ("age_s", "parent"),
    "warm_prefill_widths": ("age_s", "widths"),
    "executable_load": ("age_s", "program", "width", "bytes", "trace_s",
                        "lower_s", "backend_s", "source", "own"),
    # (the marker: age_s is the build's END, build_s its seconds)
    "program_build": ("age_s", "program", "build_s", "source")}
# ... and the args such a model adds ONE A CLASS, under the names its
# ``ServedModel.cache_classes`` declare (``<class>`` below; this file knows
# no model's): of a prefill's cached_tokens what each class took from ITS
# cache (an unbounded class all of them, a window class its reach's worth);
# each class's blocks in use and the blocks its live streams have returned
# so far as their window slid (the allocator's running total).  On a
# ``prefill`` span ``<class>_blocks_returned`` (a class with a reach only)
# is what the class gave back WHILE the span's admissions were dispatched —
# its window sliding during prefill — and ``context_tokens_in_reach_<class>``
# the key rows those chunk programs may read in the class, over its layers
# (a chunk of n rows from position p: p + n of them, a bounded class no
# more than reach + n - 1), ``attend_rows_read_<class>`` the key rows their
# attends WALK, a run of query rows at a time (a model whose attend takes a
# chunk in runs — ``inference/kv_pages.attend_rows`` — reads the stream's
# rows once a run: the ratio of the two is how often).
CLASS_SPAN_ARGS = {
    "prefill": ("cached_tokens_<class>", "<class>_blocks_returned",
                "context_tokens_in_reach_<class>",
                "attend_rows_read_<class>"),
    "decode": ("<class>_blocks_live", "<class>_blocks_returned"),
}
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
_INNER = re.compile(r"^(?:[\w.-]+\()*([\w.-]*)\)*$")


def span_args(span: str, classes=()) -> Tuple[str, ...]:
    """Every arg ``span`` may carry, for a model whose classes of cache
    layers are named ``classes``."""
    return tuple(SPAN_ARGS.get(span, ())) + tuple(
        pattern.replace("<class>", name)
        for pattern in CLASS_SPAN_ARGS.get(span, ()) for name in classes)


def _varint(buf, i: int) -> Tuple[int, int]:
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        if b < 0x80:
            return val, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, Any]]:
    """(field number, value) of one protobuf message: an int for a
    varint, a memoryview for a length-delimited or fixed field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            val, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif kind == 1:
            val, i = buf[i:i + 8], i + 8
        elif kind == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {kind} in an xplane file")
        yield key >> 3, val


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf, stat_names: Dict[int, str]) -> Tuple[str, Any]:
    """One XStat -> (name, value); a ``ref_value`` names another stat
    metadata entry whose name is the string."""
    name, value = "", None
    for f, v in _fields(buf):
        if f == 1:
            name = stat_names.get(v, str(v))
        elif f == 2:
            value = struct.unpack("<d", v)[0]
        elif f == 3:
            value = v
        elif f == 4:
            value = _signed(v)
        elif f == 5:
            value = bytes(v).decode("utf-8", "replace")
        elif f == 6:
            value = bytes(v)
        elif f == 7:
            value = stat_names.get(v, "")
    return name, value


def _map_entry(buf) -> Tuple[Any, Any]:
    key = val = None
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def _plane(buf, keep: frozenset) -> Tuple[str, Dict[str, Any]]:
    name, lines, emeta, smeta = "", [], [], []
    for f, v in _fields(buf):
        if f == 2:
            name = bytes(v).decode()
        elif f == 3:
            lines.append(v)
        elif f == 4:
            emeta.append(v)
        elif f == 5:
            smeta.append(v)
    stat_names: Dict[int, str] = {}
    for entry in smeta:
        key, val = _map_entry(entry)
        stat_names[key] = next(
            (bytes(v).decode() for f, v in _fields(val) if f == 2), "")
    metadata: Dict[int, Tuple[str, Dict[str, Any]]] = {}
    for entry in emeta:
        key, val = _map_entry(entry)
        mname, stats = "", {}
        for f, v in _fields(val):
            if f == 2:
                mname = bytes(v).decode("utf-8", "replace")
            elif f == 5:
                sname, sval = _stat(v, stat_names)
                if sname in keep:
                    stats[sname] = sval
        metadata[key] = (mname, stats)
    out_lines: Dict[str, List[Tuple[int, float, float, Any]]] = {}
    for lbuf in lines:
        lname, t0_ns, events = "", 0, []
        for f, v in _fields(lbuf):
            if f == 2:
                lname = bytes(v).decode()
            elif f == 3:
                t0_ns = _signed(v)
            elif f == 4:
                events.append(v)
        rows = out_lines.setdefault(lname, [])
        for ebuf in events:
            mid = off_ps = dur_ps = 0
            stats = None
            for f, v in _fields(ebuf):
                if f == 1:
                    mid = v
                elif f == 2:
                    off_ps = _signed(v)
                elif f == 3:
                    dur_ps = _signed(v)
                elif f == 4:
                    stats = stats or []
                    stats.append(v)
            rows.append((mid, t0_ns + off_ps / 1e3, dur_ps / 1e3, stats))
    return name, {"lines": out_lines, "metadata": metadata,
                  "stat_names": stat_names}


def read_xspace(path: str, keep_metadata_stats=("tf_op", "hlo_category")
                ) -> Dict[str, Dict[str, Any]]:
    """{plane name: {"lines": {line name: [(metadata id, start_ns,
    duration_ns, raw stats or None)]}, "metadata": {id: (name, {stat:
    value})}, "stat_names": {id: name}}}. Times are on the line's clock
    (``timestamp_ns`` + offset): the one ``ProfileData`` reports, shared
    by the device and host planes of a session."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    keep = frozenset(keep_metadata_stats)
    return dict(_plane(v, keep) for f, v in _fields(data) if f == 1)


def event_args(raw_stats, stat_names: Dict[int, str]) -> Dict[str, Any]:
    """An event's own stats (a ``Telemetry.span``'s args) as a dict."""
    return dict(_stat(s, stat_names) for s in raw_stats or ())


def scope_of(tf_op: str) -> Tuple[Tuple[str, ...], bool, bool]:
    """``tf_op`` -> (scope path: the SCOPES names in order, backward?,
    recomputed?). JAX wraps a scope's name in the transforms applied
    under it (``transpose(jvp(attn))``); a repeat of the scope before it
    (``fwd_bwd/transpose(fwd_bwd)``) is one scope. Backward operations
    carry ``transpose(``, recomputed ones ``rematted_computation``."""
    op = (tf_op or "").split(";", 1)[0].rstrip(":")
    path: List[str] = []
    for part in op.split("/"):
        m = _INNER.match(part)
        if m and m.group(1) in SCOPES and path[-1:] != [m.group(1)]:
            path.append(m.group(1))
    return tuple(path), "transpose(" in op, "rematted_computation" in op


def instruction_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``: a TPU
    trace names an op by its whole HLO line."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def device_op_events(path: str) -> List[Dict[str, Any]]:
    """The capture's device operations as Chrome-trace-shaped events
    (``ph: "X"``, microseconds; one ``pid`` per device plane) for
    ``profile_ingest.ingest_events``, each with ``args``: ``hlo_op`` (the
    instruction's name), ``hlo_category``, ``tf_op``, ``scope`` (the
    named-scope path, ``/``-joined), ``backward``, ``recomputed``, and
    ``device_plane``. Empty for a capture without a TPU plane."""
    events: List[Dict[str, Any]] = []
    for pname, plane in read_xspace(path).items():
        m = DEVICE_PLANE.match(pname)
        if not m:
            continue
        pid = int(m.group(1))
        events.append({"ph": "M", "name": "thread_name", "pid": pid,
                       "tid": 0, "args": {"name": f"{pname} {OPS_LINE}"}})
        for mid, start, dur, _ in plane["lines"].get(OPS_LINE, []):
            name, stats = plane["metadata"].get(mid, ("", {}))
            tf_op = stats.get("tf_op", "")
            scope, backward, recomputed = scope_of(tf_op)
            op = instruction_name(name)
            events.append({
                "ph": "X", "name": op, "pid": pid, "tid": 0,
                "ts": start / 1e3, "dur": dur / 1e3,
                "args": {"hlo_op": op, "tf_op": tf_op,
                         "hlo_category": stats.get("hlo_category", ""),
                         "scope": "/".join(scope), "backward": backward,
                         "recomputed": recomputed, "device_plane": True}})
    return events


def idle_gaps(xspace: Dict[str, Dict[str, Any]], min_ms: float = 1.0
              ) -> List[Dict[str, Any]]:
    """The intervals of at least ``min_ms`` in which no operation ran on
    a device, between its first and its last: ``{"device", "start_ns",
    "end_ns"}`` on the capture's clock, in order of time."""
    gaps: List[Dict[str, Any]] = []
    for pname, plane in xspace.items():
        m = DEVICE_PLANE.match(pname)
        if not m:
            continue
        reach = None
        for _, start, dur, _ in sorted(plane["lines"].get(OPS_LINE, []),
                                       key=lambda e: e[1]):
            if reach is not None and start - reach >= min_ms * 1e6:
                gaps.append({"device": int(m.group(1)),
                             "start_ns": reach, "end_ns": start})
            reach = start + dur if reach is None else max(reach,
                                                          start + dur)
    return sorted(gaps, key=lambda g: g["start_ns"])


def ledger_clock_offset_ns(xspace: Dict[str, Dict[str, Any]]) -> Any:
    """Capture clock minus the start-up ledger's (process age), in ns,
    from any host event that carries ``age_s`` (the ledger's spans: their
    start; a ``program_build`` marker: the instant it was left); None
    for a capture without one."""
    for pname, plane in xspace.items():
        if DEVICE_PLANE.match(pname):
            continue
        for rows in plane["lines"].values():
            for mid, start, _, stats in rows:
                if stats and plane["metadata"].get(mid, ("",))[0] in SPANS:
                    age = event_args(stats, plane["stat_names"]).get("age_s")
                    if age is not None:
                        return start - float(age) * 1e9
    return None


def named_idle_gaps(xspace: Dict[str, Dict[str, Any]],
                    ledger_rows: List[Dict[str, Any]], min_ms: float = 1.0,
                    offset_ns: Any = None) -> List[Dict[str, Any]]:
    """``idle_gaps`` of a capture (``read_xspace``'s), each with
    ``program``: the programs of the start-up ledger's ``program_build``
    rows (``monitor.startup.rows()``) that overlap it, space-joined — a
    recompile inside a traced window is a named gap — and ``build_s``,
    the seconds of the overlap; both absent from a gap no build touches,
    and from every gap of a capture that holds none of the ledger's spans
    or markers to tie the clocks by (``offset_ns``: the capture's clock
    less the ledger's, where the caller knows it)."""
    gaps = idle_gaps(xspace, min_ms)
    if offset_ns is None:
        offset_ns = ledger_clock_offset_ns(xspace)
    if offset_ns is None:
        return gaps
    builds = [(r["start_s"] * 1e9 + offset_ns, r["end_s"] * 1e9 + offset_ns,
               r["program"]) for r in ledger_rows
              if r["kind"] == "program_build"]
    for g in gaps:
        hit = [(min(e, g["end_ns"]) - max(s, g["start_ns"]), p)
               for s, e, p in builds
               if s < g["end_ns"] and e > g["start_ns"]]
        if hit:
            g["program"] = " ".join(dict.fromkeys(p for _, p in hit))
            g["build_s"] = sum(o for o, _ in hit) / 1e9
    return gaps
