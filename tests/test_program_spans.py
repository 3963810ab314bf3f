"""The program's own spans and scopes in the JAX profiler's trace.

- ``Telemetry.span`` always opens a ``jax.profiler`` annotation: bare when
  nothing else is configured, and as well as the Chrome-trace span / the
  checkpoint ledger bucket when they are.
- The compiled train, decode and prefill programs carry the
  ``jax.named_scope`` names in their ``op_name`` metadata: one case per
  scope, so a refactor that drops one fails by name.
- A profiler session round serve iterations and ``train_batch`` calls
  holds every host span of docs/tutorials/telemetry.md with its args,
  each once per occurrence.

Span and scope names are a contract with the benchmark's readers
(``perfbench/lib/program_trace.py``) and with operators' dashboards.
"""
import collections
import glob
import json
import os
import re
import types

import jax
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference import (InferenceEngine, Request,
                                     synthetic_requests)
from deepspeed_tpu.inference import scheduler as scheduler_mod
from deepspeed_tpu.models.gpt2 import (GPT2_CONFIGS, gpt2_init,
                                       gpt2_loss_fn)
from deepspeed_tpu.monitor.telemetry import Telemetry
from deepspeed_tpu.parallel.topology import build_mesh
from deepspeed_tpu.runtime.config import DeepSpeedConfig

from simple_model import base_config

CFG = GPT2_CONFIGS["gpt2-tiny"]


def _annotations(trace_dir):
    """{span name: [{arg: value}]} of the profiler session's host
    events, in time order per name."""
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[-1]
    out = collections.defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                out[ev.name].append((ev.start_ns, ev.duration_ns,
                                     dict(ev.stats)))
    return {k: sorted(v, key=lambda e: e[0]) for k, v in out.items()}


def _session(trace_dir, fn):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    return _annotations(str(trace_dir))


# --------------------------------------------------------------------- #
# (a) Telemetry.span
# --------------------------------------------------------------------- #
def _telemetry(tmp_path, kind):
    knobs = {"disabled": {"enabled": False},
             "tracer": {"enabled": True, "output_path": str(tmp_path),
                        "trace_path": str(tmp_path / "host.trace.json")},
             "checkpoint-bucket": {"enabled": True,
                                   "output_path": str(tmp_path)}}[kind]
    cfg = DeepSpeedConfig(base_config(telemetry=knobs)).telemetry_config
    return Telemetry(cfg, default_report_steps=1, is_writer=True)


@pytest.mark.parametrize("kind", ["disabled", "tracer", "checkpoint-bucket"])
def test_span_always_opens_a_profiler_annotation(tmp_path, kind):
    tel = _telemetry(tmp_path, kind)
    name = "checkpoint_save" if kind == "checkpoint-bucket" else "decode"
    if kind == "disabled":
        # nothing but the annotation is allocated
        assert type(tel.span(name, iteration=3)) is \
            jax.profiler.TraceAnnotation
        assert tel.tracer is None and tel.ledger is None
        assert type(tel.span("train_batch", step_num=5)) is \
            jax.profiler.StepTraceAnnotation
    if kind == "checkpoint-bucket":
        assert tel.tracer is None
        # a span with no bucket stays the bare annotation
        assert type(tel.span("decode")) is jax.profiler.TraceAnnotation

    def use():
        with tel.span(name, iteration=3) as sp:
            sp.set_metadata(live_blocks=7)
    found = _session(tmp_path / "prof", use)
    assert [a for _, _, a in found[name]] == \
        [{"iteration": 3, "live_blocks": 7}]
    if kind == "tracer":
        tel.tracer.flush()
        tel.close()
        events = json.load(open(tmp_path / "host.trace.json"))
        assert events[0]["name"] == "clock_sync"
        ours = [e for e in events if e["name"] == name]
        assert len(ours) == 1 and ours[0]["ph"] == "X"
        assert ours[0]["args"] == {"iteration": 3, "live_blocks": 7}
    elif kind == "checkpoint-bucket":
        assert tel.ledger.peek()["noted_s"]["checkpoint"] > 0
        tel.close()


# --------------------------------------------------------------------- #
# (b) device scopes in the compiled programs
# --------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def params():
    return gpt2_init(jax.random.PRNGKey(1), CFG)


@pytest.fixture(scope="module")
def train_engine(params):
    ds = {"train_batch_size": 2, "train_micro_batch_size_per_gpu": 2,
          "gradient_accumulation_steps": 1, "gradient_clipping": 1.0,
          "bf16": {"enabled": True}, "zero_optimization": {"stage": 2},
          "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
          "steps_per_print": 10 ** 9}
    engine, _, _, _ = deepspeed_tpu.initialize(
        config=ds, model=gpt2_loss_fn(CFG), model_params=params,
        mesh=build_mesh(devices=jax.devices()[:1]))
    return engine


def _batch(seed=0):
    return np.random.default_rng(seed).integers(
        0, CFG.vocab_size, (2, 33)).astype(np.int32)


def _op_names(jitted, *args):
    text = jitted.lower(*args).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', text))


@pytest.fixture(scope="module")
def train_op_names(train_engine):
    train_engine.train_batch(_batch())          # builds the step
    return _op_names(train_engine._train_step_fn, train_engine.state,
                     _batch(), train_engine._base_rng)


@pytest.mark.parametrize("scope", [
    "fwd_bwd", "transpose(", "fwd_bwd/transpose(", "embed", "attn", "mlp",
    "lm_head", "attn/dropout", "mlp/dropout", "optimizer/flatten",
    "optimizer/norm", "optimizer/kernel", "optimizer/unflatten"])
def test_train_step_carries_scope(train_op_names, scope):
    assert any(scope in n for n in train_op_names), scope


def test_backward_ops_sit_under_fwd_bwd_and_optimizer_ops_do_not(
        train_op_names):
    assert not any("transpose(" in n and "fwd_bwd" not in n
                   for n in train_op_names)
    assert not any("optimizer" in n and "fwd_bwd" in n
                   for n in train_op_names)


@pytest.fixture(scope="module")
def serve_engine(params):
    eng = InferenceEngine(CFG, params, config={"inference": {
        "max_slots": 4, "max_seq_len": 64, "prefill_chunk": 8,
        "block_size": 16, "paged_kernel": True}},
        mesh=build_mesh(devices=jax.devices()[:1]))
    yield eng
    eng.close()


@pytest.fixture(scope="module")
def serve_op_names(serve_engine):
    eng = serve_engine
    G, J = eng.dp, eng.cache_spec.max_blocks_per_slot
    key, temp = eng._next_key(), np.float32(0.0)
    k, v = eng.cache["k"], eng.cache["v"]
    decode = _op_names(eng._decode_fn, eng._params, k, v, eng._no_fetch,
                       eng.last_tokens, np.ones(eng.max_slots, bool),
                       eng.lengths, eng.block_tables, key, temp)
    prefill = _op_names(
        eng._prefill_fn, eng._params, k, v,
        np.zeros((G, eng.prefill_chunk), np.int32),
        np.zeros((G, J), np.int32), np.zeros(G, np.int32),
        np.zeros(G, np.int32), np.ones(G, np.int32), np.int32(1), key,
            temp)
    copy = _op_names(eng._copy_fn, k, v, np.zeros(G, np.int32),
                     np.ones(G, np.int32))
    return {"decode": decode, "prefill": prefill, "copy": copy}


@pytest.mark.parametrize("program,scope", [
    ("decode", "kv_write"), ("decode", "attend"), ("decode", "sample"),
    ("decode", "embed"), ("decode", "mlp"), ("decode", "lm_head"),
    ("prefill", "kv_write"), ("prefill", "attend"), ("prefill", "sample"),
    ("copy", "cow_copy")])
def test_serve_program_carries_scope(serve_op_names, program, scope):
    assert any(f"/{scope}" in n for n in serve_op_names[program]), \
        (program, scope)


def test_kv_write_and_attend_nest_under_attn(serve_op_names):
    for program in ("decode", "prefill"):
        for scope in ("kv_write", "attend"):
            assert any(f"attn/{scope}" in n
                       for n in serve_op_names[program]), (program, scope)


# --------------------------------------------------------------------- #
# (c) host spans in a profiler session
# --------------------------------------------------------------------- #
SERVE_SPANS = {
    "admit": {"queued", "admitted", "late_ms", "rids"},
    "prefill": {"slots", "prompt_tokens", "cached_tokens", "chunks",
                "rows_computed", "chain_walks", "rids"},
    "prefill_plan": set(),
    "prefill_chunk": {"ci", "active_groups", "rows"},
    "prefill_fetch": set(),
    # (of every ``decode`` span; the ones that dispatched an iteration
    # carry DISPATCHED too: the loop runs an iteration ahead of its token
    # fetch, so a span holds the dispatch of one iteration and the fetch
    # of the one before, or only one of the two at a stretch's ends)
    "decode": {"iteration", "active", "ahead", "dropped"},
    "decode_tables": set(), "decode_dispatch": set(),
    "decode_fetch": set(), "decode_advance": set(),
    # (``finished`` too, where a request finished: an empty arg is not
    # recorded)
    "emit": {"row", "streams", "continuing", "gap_ms", "stall_ms",
             "host_ms"},
    "serve_idle": {"why"}}
DISPATCHED = {"live_blocks", "context_tokens", "attend_steps",
              "attend_live_steps", "attend_cold_steps"}
TRAIN_SPANS = {"train_batch": {"step_num"}, "data_prep": {"step"},
               "step_dispatch": {"step"}, "step_log": {"step"}}
# The call's row of the training timeline (monitor/training.py), which
# ``train_batch`` carries while something records spans.
TRAIN_ROW_ARGS = ("row", "gap_ms", "outside_ms", "host_ms", "data_ms",
                  "dispatch_ms", "log_ms", "in_flight", "completed", "built")


def _dispatched(found):
    """The args of the ``decode`` spans that dispatched an iteration."""
    return [a for _, _, a in found["decode"] if "context_tokens" in a]


@pytest.fixture(scope="module")
def serve_annotations(serve_engine, tmp_path_factory):
    eng = serve_engine
    rng = np.random.default_rng(3)
    # warm up (compiles) outside the session
    eng.serve(synthetic_requests(2, prompt_len=(10, 12), max_new_tokens=3,
                                 vocab_size=CFG.vocab_size))
    # two requests at once (the second arrival late enough for an idle
    # sleep), 3 tokens each: one prefill token + two decode iterations
    # for the first batch, then the same for the late one.  The serving
    # loop's clock is driven (a tick a reading, a jump a sleep), so the
    # first batch is over long before the late arrival however loaded
    # the machine is, and nothing waits.
    reqs = [Request(rid=100 + i, max_new_tokens=3, arrival_s=a,
                    prompt=rng.integers(0, CFG.vocab_size, 12,
                                        dtype=np.int32))
            for i, a in enumerate((0.0, 0.0, 2.0))]
    now = [0.0]

    def clock():
        now[0] += 1e-5
        return now[0]

    def sleep(s):
        now[0] += s
    real = eng.serving.clock
    eng.serving.clock = clock
    eng.reset_serving_stats()        # a new aggregator on the same clock
    report = {}
    try:
        with pytest.MonkeyPatch.context() as mp:
            # the idle wait is all the scheduler asks of ``time``
            mp.setattr(scheduler_mod, "time",
                       types.SimpleNamespace(sleep=sleep))
            found = _session(
                tmp_path_factory.mktemp("serve_prof"),
                lambda: report.update(eng.serve(reqs, idle_sleep_s=0.5)))
    finally:
        eng.serving.clock = real
    assert report["completed"] == 3 and now[0] < 3.0
    return found, report


@pytest.mark.parametrize("span", sorted(SERVE_SPANS))
def test_serve_span_is_in_the_profile_with_its_args(serve_annotations,
                                                    span):
    found, _ = serve_annotations
    assert found.get(span), f"no {span!r} span in the profiler session"
    for _, _, args in found[span]:
        assert SERVE_SPANS[span] <= set(args), (span, args)


def test_serve_spans_occur_once_per_occurrence(serve_annotations):
    found, report = serve_annotations
    iters = report["iterations"]
    assert iters >= 3
    for name in ("decode_tables", "decode_dispatch", "decode_fetch",
                 "decode_advance", "emit"):
        assert len(found[name]) == iters, (name, len(found[name]), iters)
    # Two stretches (the pair at once, the late arrival), each of two
    # iterations: dispatch A | dispatch B + fetch A | fetch B. Each
    # iteration is described once, by the span that dispatched it.
    decodes = [a for _, _, a in found["decode"]]
    assert len(decodes) == iters + 2
    assert all(DISPATCHED <= set(a) for a in _dispatched(found))
    assert [a["iteration"] for a in _dispatched(found)] == \
        list(range(decodes[0]["iteration"], decodes[0]["iteration"] + iters))
    assert [a["ahead"] for a in decodes] == [0, 1, 0] * 2
    assert [DISPATCHED <= set(a) for a in decodes] == [True, True, False] * 2
    assert all(a["dropped"] == 0 for a in decodes)
    assert report["lookahead_share"] == 0.5      # B of A, B; D of C, D
    assert report["lookahead_dropped_rows"] == 0
    # one prefill per admission batch (one chip: one slot per batch)
    admitted = [a for _, _, a in found["admit"] if a["admitted"]]
    assert len(admitted) == len(found["prefill"]) == 3
    assert len(found["prefill_plan"]) == len(found["prefill_fetch"]) == 3
    assert len(found["prefill_chunk"]) == \
        sum(a["chunks"] for _, _, a in found["prefill"])
    # the benchmark's own wrapper names are not the program's
    assert not {"prefill_many", "decode_once", "data", "wait"} & set(found)


def test_serve_spans_nest_and_follow_a_request(serve_annotations):
    found, _ = serve_annotations

    def within(inner, outer):
        return any(o[0] <= inner[0] and
                   inner[0] + inner[1] <= o[0] + o[1] for o in outer)
    for child in ("decode_tables", "decode_dispatch", "decode_fetch",
                  "decode_advance"):
        assert all(within(e, found["decode"]) for e in found[child]), child
    for child in ("prefill_plan", "prefill_chunk", "prefill_fetch"):
        assert all(within(e, found["prefill"]) for e in found[child]), child
    # rid 102 is admitted, prefilled and finished under its own id
    rid = 102
    assert any(str(rid) in str(a.get("rids", "")).split()
               for _, _, a in found["admit"])
    assert any(str(rid) in str(a.get("rids", "")).split()
               for _, _, a in found["prefill"])
    assert any(str(rid) in str(a.get("finished", "")).split()
               for _, _, a in found["emit"])
    # the late arrival was polled for at or after its due time
    assert all(a["late_ms"] >= 0 for _, _, a in found["admit"])
    assert {a["why"] for _, _, a in found["serve_idle"]} == {"no_arrival"}
    # the dispatched iteration's args: what _cache_accounting read
    assert all(a["live_blocks"] > 0 and a["context_tokens"] > 0
               for a in _dispatched(found))


def test_emit_and_prefill_spans_carry_the_timeline(serve_annotations,
                                                   serve_engine):
    """The rows of ``monitor/serving.py`` on the profiler's clock: an
    ``emit`` names its row, and a ``prefill`` the rows its chunk programs
    computed (two chunks of 8 for a 12-token prompt, one group)."""
    found, report = serve_annotations
    emits = [a for _, _, a in found["emit"]]
    assert [a["row"] for a in emits] == list(range(report["iterations"]))
    # a row is an iteration, in the order of their dispatch (the engine
    # counts its iterations from its start, the rows from
    # ``reset_serving_stats``)
    assert len({d["iteration"] - a["row"]
                for d, a in zip(_dispatched(found), emits)}) == 1
    assert all(a["gap_ms"] > 0 and a["stall_ms"] >= 0 for a in emits)
    assert [(a["streams"], a["continuing"]) for a in emits] == \
        [(2, 0), (2, 2), (1, 0), (1, 1)]
    # the prefills lie in the interval of the row they precede; the idle
    # wait ahead of the late arrival is nobody's interval
    assert emits[0]["stall_ms"] > 0 and emits[1]["stall_ms"] == 0
    assert emits[2]["gap_ms"] > 1000 and emits[2]["continuing"] == 0
    for _, _, a in found["prefill"]:
        # (a chunk of 8 is its own one width: inference/engine.py's
        # ``prefill_widths``; tests/test_prefill_widths.py has the ladder)
        assert a["rows_computed"] == a["chunks"] * serve_engine.prefill_chunk
        assert a["prompt_tokens"] - a["cached_tokens"] == 12
        # the scheduler's Request keeps its chain: one walk a request
        assert a["chain_walks"] == a["slots"] == 1
    assert report["prefill_row_fill"] == 0.75
    assert report["itl_ms"]["n"] == 3 * 2 and report["stalls"] == []
    split = report["itl_split_ms"]
    assert sum(split.values()) == pytest.approx(report["itl_ms"]["mean"],
                                                abs=1e-9)


def test_decode_span_counts_the_attend_steps(serve_annotations,
                                             serve_engine):
    """By hand: every context here is under one block of 16 positions,
    so a stream that holds a block is ONE group of table slots (a live
    step) and each of the other slots is one empty grid step; the tiny
    model's heads are one head block. The running ratio is in the
    serving snapshot."""
    found, report = serve_annotations
    args = _dispatched(found)
    assert all(a["attend_steps"] == serve_engine.max_slots for a in args)
    assert all(a["attend_live_steps"] == a["active"] for a in args)
    assert {a["active"] for a in args} == {1, 2}
    # the active slots are neighbours from slot 0 on: the first of the
    # call starts cold, the one after it behind it
    assert all(a["attend_cold_steps"] == 1 for a in args)
    assert report["attend_live_step_share"] == pytest.approx(
        sum(a["attend_live_steps"] for a in args) /
        sum(a["attend_steps"] for a in args), abs=1e-4)
    assert report["attend_cold_step_share"] == pytest.approx(
        len(args) / sum(a["attend_live_steps"] for a in args), abs=1e-4)


def test_the_readers_list_names_the_same_args():
    from deepspeed_tpu.monitor.xplane_reader import SPAN_ARGS, SPANS
    assert set(SPAN_ARGS) <= set(SPANS)
    for span, args in {**SERVE_SPANS, **TRAIN_SPANS}.items():
        assert args <= set(SPAN_ARGS.get(span, ())), span
    assert set(TRAIN_ROW_ARGS) <= set(SPAN_ARGS["train_batch"])
    assert DISPATCHED <= set(SPAN_ARGS["decode"])


# The start-up spans (``monitor/startup.py``: each also a row of the
# start-up ledger, read back from a profiler session with these args in
# ``tests/test_startup_ledger.py``), and the marker a build row leaves.
STARTUP_SPANS = {
    "engine_init": {"age_s", "mode", "param_bytes"},
    "place_params": {"age_s", "parent"},
    "allocate_cache": {"age_s", "parent"},
    "shard_state": {"age_s", "parent"},
    "warm_prefill_widths": {"age_s", "widths"},
    "executable_load": {"age_s", "program", "width", "bytes", "source"},
    "program_build": {"age_s", "program", "build_s", "source"}}


@pytest.mark.parametrize("span", sorted(STARTUP_SPANS))
def test_a_startup_span_is_on_the_readers_list(span, tmp_path):
    from deepspeed_tpu.monitor import startup
    from deepspeed_tpu.monitor.xplane_reader import SPAN_ARGS, SPANS
    assert span in SPANS and STARTUP_SPANS[span] <= set(SPAN_ARGS[span])
    if span == "program_build":
        return                  # (JAX's events write it, not ``span``)
    args = {a: 1 for a in STARTUP_SPANS[span] - {"age_s"}}

    def use():
        with startup.span(span, **args) as row:
            row["late"] = 2
    found = _session(tmp_path / "prof", use)
    (_, _, got), = found[span]
    row = startup.rows()[-1]
    assert got == {**args, "late": 2, "age_s": pytest.approx(row["start_s"])}
    assert row == {"kind": span, **args, "late": 2,
                   "start_s": row["start_s"], "end_s": row["end_s"]}
    assert row["end_s"] >= row["start_s"]


def test_attend_step_counts_from_live_blocks():
    from deepspeed_tpu.ops.paged_attention import attend_step_counts
    # the serve cell's decode: all 20 heads of 16 table slots a step;
    # 25 live blocks are two groups, 16 one, 33 three, 0 an empty step
    cell = dict(num_heads=20, head_dim=64, block_size=16, table_width=64,
                kv_itemsize=2)
    assert attend_step_counts([25, 16, 0, 0, 33], K=1, **cell) == (8, 6)
    assert attend_step_counts([0, 0], K=1, **cell) == (2, 0)
    # a prefill chunk runs fewer heads a step: more head blocks
    steps, live = attend_step_counts([16], K=128, **cell)
    assert steps == live and steps > 1 and 20 % steps == 0


def test_attend_cold_steps_by_hand():
    """A live stream starts cold as the first of its call or after a
    dead one; every shard of a dp mesh is a call of its own."""
    from deepspeed_tpu.ops.paged_attention import attend_cold_steps
    live = [25, 16, 3, 0, 33, 1, 0, 7]
    assert attend_cold_steps(live) == 3                  # slots 0, 4, 7
    assert attend_cold_steps(live, calls=2) == 3         # 0 | 4, 7
    assert attend_cold_steps(live, calls=4) == 4         # 0 | 2 | 4 | 7
    assert attend_cold_steps([0, 0, 0]) == 0
    assert attend_cold_steps([3] * 256) == 1


@pytest.mark.parametrize("K,slots,by_hand", [
    # `serve.trinity-mini.mixed-docqa-over`'s full class: 4 K/V heads of
    # 128 (one head block), blocks of 64, a table of 528, bf16. Decode's 8
    # query rows a K/V head walk SIXTEEN slots a group (2 MiB of K and V
    # tiles): 500 live blocks are 32 groups, 33 three, 16 and 1 one, a
    # dead stream one empty step ...
    (8, 16, (38, 37)),
    # ... a prefill run's 512 rows take the chunk body (PR 65) at eight
    # slots = 512 keys a group: 63 + 5 + 2 + 1 groups.
    (512, 8, (72, 71)),
])
def test_attend_step_counts_at_wide_grouped_heads(K, slots, by_hand):
    from deepspeed_tpu.ops.paged_attention import (_tile_rule,
                                                   attend_step_counts)
    cell = dict(num_heads=4, head_dim=128, block_size=64, table_width=528,
                kv_itemsize=2)
    assert _tile_rule(K, 4, 128, 64, 528, 2) == (4, slots)
    assert attend_step_counts([500, 33, 16, 0, 1], K=K, **cell) == by_hand


@pytest.fixture(scope="module")
def train_annotations(train_engine, tmp_path_factory):
    train_engine.train_batch(_batch())          # compiled before
    first = train_engine.global_steps

    def two_steps():
        for i in range(2):
            train_engine.train_batch(_batch(i))
    return _session(tmp_path_factory.mktemp("train_prof"),
                    two_steps), first


@pytest.mark.parametrize("span", sorted(TRAIN_SPANS))
def test_train_span_is_in_the_profile_once_per_step(train_annotations,
                                                    span):
    found, first = train_annotations
    assert len(found.get(span, [])) == 2, (span, found.get(span))
    key = "step_num" if span == "train_batch" else "step"
    assert [a[key] for _, _, a in found[span]] == [first, first + 1]
    if span != "train_batch":
        outer = found["train_batch"]
        assert all(any(o[0] <= e[0] and e[0] + e[1] <= o[0] + o[1]
                       for o in outer) for e in found[span])


@pytest.mark.parametrize("arg", TRAIN_ROW_ARGS)
def test_train_batch_carries_the_timelines_row(train_annotations,
                                               train_engine, arg):
    from deepspeed_tpu.monitor.training import COL
    from deepspeed_tpu.monitor.xplane_reader import SPAN_ARGS
    found, _ = train_annotations
    spans = [a for _, _, a in found["train_batch"]]
    assert len(spans) == 2 and all(arg in a for a in spans), spans
    assert arg in SPAN_ARGS["train_batch"]
    table = train_engine.timeline.table()
    for a in spans:
        assert isinstance(a[arg], (int, float))
        r = table[a["row"]]
        assert r[COL["step"]] == a["step_num"]
        if arg.endswith("_ms") and arg != "host_ms":
            assert a[arg] == pytest.approx(r[COL[arg[:-2] + "s"]] * 1e3,
                                           abs=1e-4)
        elif arg != "host_ms" and arg != "row":
            assert a[arg] == r[COL[arg]]
    if arg == "row":
        assert spans[1]["row"] == spans[0]["row"] + 1
    if arg == "host_ms":
        assert all(a["host_ms"] == pytest.approx(
            a["data_ms"] + a["dispatch_ms"] + a["log_ms"], abs=1e-3)
            for a in spans)
    if arg == "built":
        assert [a["built"] for a in spans] == [0, 0]     # compiled before


def _within(span_ns: float, part_ms: float) -> bool:
    """A span's duration (the profiler's clock) against a part of the row
    (``time.perf_counter``) that contains it in real time: two clocks, of
    which the profiler's wall clock may be slewed by up to 500 ppm, so 1 us
    + 0.1% of slack."""
    return span_ns / 1e6 <= part_ms * 1.001 + 1e-3


def test_the_child_spans_lie_inside_the_rows_clock_reads(train_annotations):
    """What ``train_batch`` guarantees by the ORDER of its clock reads: each
    of the row's three parts runs from a read before its span opens to a
    read after it closes, so the span's own duration is at most the part,
    and ``host_ms``, their sum, at least the three spans'.  ``host_ms`` is
    NOT held under ``train_batch``'s own duration: the row is entered
    before that span opens (``enter``, the profiler tick and the span's
    construction lie between the two), so a thread descheduled there — a
    busy test worker — adds to ``host_ms`` and not to the span.  (That
    comparison, under 1 us of slack, is what failed in the driver's run of
    PR 53's tree.)"""
    found, _ = train_annotations
    for i, (_, _, a) in enumerate(found["train_batch"]):
        children = 0.0
        for span, part in (("data_prep", "data_ms"),
                           ("step_dispatch", "dispatch_ms"),
                           ("step_log", "log_ms")):
            assert _within(found[span][i][1], a[part]), (span, a)
            children += found[span][i][1]
        assert _within(children, a["host_ms"]), a
        # the spans themselves are ordered: the children inside the parent
        start, dur = found["train_batch"][i][:2]
        assert all(start <= found[span][i][0]
                   and found[span][i][0] + found[span][i][1] <= start + dur
                   for span in ("data_prep", "step_dispatch", "step_log"))


# --------------------------------------------------------------------- #
# (d) a served model with an expert layer (PR 32): scopes and counters
# --------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def latent_engine():
    from deepspeed_tpu.models.deepseek_v3 import deepseek_v3_init
    from test_latent_serving import tiny
    cfg = tiny(held=(4, 8))
    eng = InferenceEngine(
        cfg, deepseek_v3_init(jax.random.PRNGKey(0), cfg),
        config={"inference": {"max_slots": 4, "max_seq_len": 64,
                              "prefill_chunk": 8, "block_size": 16,
                              "paged_kernel": True}},
        mesh=build_mesh(devices=jax.devices()[:1]))
    yield eng
    eng.close()


@pytest.fixture(scope="module")
def latent_op_names(latent_engine):
    eng = latent_engine
    G, J = eng.dp, eng.cache_spec.max_blocks_per_slot
    key, temp = eng._next_key(), np.float32(0.0)
    pool = eng.cache["latent"]
    return {
        "decode": _op_names(eng._decode_fn, eng._params, pool,
                            eng._no_fetch, eng.last_tokens,
                            np.ones(eng.max_slots, bool), eng.lengths,
                            eng.block_tables, key, temp),
        "prefill": _op_names(
            eng._prefill_fn, eng._params, pool,
            np.zeros((G, eng.prefill_chunk), np.int32),
            np.zeros((G, J), np.int32), np.zeros(G, np.int32),
            np.zeros(G, np.int32), np.ones(G, np.int32), np.int32(1), key,
            temp)}


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("scope", [
    "embed", "attn/latent_proj", "attn/kv_write", "attn/attend", "mlp",
    "moe/router", "moe/dispatch", "moe/experts", "moe/combine",
    "moe/shared", "lm_head", "sample"])
def test_latent_program_carries_scope(latent_op_names, program, scope):
    assert any(f"/{scope}" in n for n in latent_op_names[program]), \
        (program, scope)


def test_the_readers_list_names_the_latent_scopes_and_counters():
    from deepspeed_tpu.monitor.xplane_reader import (SCOPES, SPAN_ARGS,
                                                     scope_of)
    assert {"latent_proj", "moe", "router", "dispatch", "experts",
            "combine", "shared"} <= set(SCOPES)
    assert scope_of("jit(decode_step)/while/body/moe/experts/x")[0] == \
        ("moe", "experts")
    moe = {"moe_held_pairs", "moe_held_max", "moe_held_mean",
           "moe_held_empty", "moe_held_pair_share"}
    assert moe <= set(SPAN_ARGS["decode"]) and moe <= set(SPAN_ARGS["prefill"])


def test_decode_and_prefill_spans_carry_the_expert_counters(tmp_path,
                                                            latent_engine):
    """The counters ride the token fetch: the same number of device
    fetches an iteration as GPT-2 pays, and the spans carry what the held
    experts got."""
    eng = latent_engine
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, 250, size=11 + i,
                                               dtype=np.int32),
                    max_new_tokens=5, arrival_s=0.0) for i in range(3)]
    report = {}
    found = _session(tmp_path, lambda: report.update(eng.serve(reqs)))
    cfg = eng.model_cfg
    # a ``decode`` span carries the counters of the iteration it FETCHED
    # (the first of a stretch fetched none): each iteration's once
    fetched = [a for _, _, a in found["decode"] if "moe_held_pairs" in a]
    assert len(fetched) == report["iterations"] == len(_dispatched(found))
    for args in (fetched, [a for _, _, a in found["prefill"]]):
        assert args and all(a["moe_held_pairs"] > 0 for a in args)
        for a in args:
            assert a["moe_held_max"] >= a["moe_held_mean"] > 0
            assert 0 < a["moe_held_pair_share"] <= 1
            assert 0 <= a["moe_held_empty"] <= \
                cfg.num_moe_layers * cfg.held[1] * 8
    # decode: rows = active slots, so pairs <= active x k x expert layers
    # (``active`` is on the span that dispatched the iteration)
    for a, d in zip(fetched, _dispatched(found)):
        assert a["moe_held_pairs"] <= d["active"] * 4 * cfg.num_moe_layers
    means = eng.serving.snapshot()["model_counters"]
    assert set(means) >= {"moe_held_pairs", "moe_held_max",
                          "moe_held_mean", "moe_held_empty",
                          "moe_held_pair_share"}


# --------------------------------------------------------------------- #
# (g) the retention family's scopes (PR 34): one case a program and scope
# --------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def retention_op_names():
    import jax.numpy as jnp
    from deepspeed_tpu.models.brumby import BrumbyConfig, brumby_init
    cfg = BrumbyConfig(vocab_size=128, hidden_size=32, intermediate_size=64,
                       num_hidden_layers=2, num_attention_heads=4,
                       num_key_value_heads=2, head_dim=16,
                       max_position_embeddings=128, dtype=jnp.float32)
    eng = InferenceEngine(
        cfg, brumby_init(jax.random.PRNGKey(0), cfg),
        config={"inference": {"max_slots": 4, "max_seq_len": 128,
                              "prefill_chunk": 16, "block_size": 8,
                              "num_blocks": 6, "paged_kernel": True}},
        mesh=build_mesh(devices=jax.devices()[:1]))
    G, J = eng.dp, eng.cache_spec.max_blocks_per_slot
    assert J == 1 and list(eng._cache_sh) == ["state", "norm"]
    key, temp = eng._next_key(), np.float32(0.0)
    pools = eng._pools()
    names = {
        "decode": _op_names(eng._decode_fn, eng._params, *pools,
                            eng._no_fetch, eng.last_tokens,
                            np.ones(eng.max_slots, bool), eng.lengths,
                            eng.block_tables, key, temp),
        "prefill": _op_names(
            eng._prefill_fn, eng._params, *pools,
            np.zeros((G, eng.prefill_chunk), np.int32),
            np.zeros((G, J), np.int32), np.zeros(G, np.int32),
            np.zeros(G, np.int32), np.ones(G, np.int32), np.int32(1), key,
            temp),
        "copy": _op_names(eng._copy_fn, *pools, np.zeros(G, np.int32),
                          np.ones(G, np.int32))}
    eng.close()
    return names


@pytest.mark.parametrize("program,scope", [
    ("decode", "embed"), ("decode", "attn/qkv_proj"),
    ("decode", "attn/state_update"), ("decode", "attn/out_proj"),
    ("decode", "mlp"), ("decode", "lm_head"), ("decode", "sample"),
    ("prefill", "embed"), ("prefill", "attn/qkv_proj"),
    ("prefill", "attn/retention_chunk"), ("prefill", "attn/out_proj"),
    ("prefill", "mlp"), ("prefill", "lm_head"), ("prefill", "sample"),
    ("copy", "state_copy")])
def test_retention_program_carries_scope(retention_op_names, program,
                                         scope):
    assert any(f"/{scope}" in n for n in retention_op_names[program]), \
        (program, scope)


def test_retention_forms_stay_in_their_own_program(retention_op_names):
    assert not any("retention_chunk" in n
                   for n in retention_op_names["decode"])
    assert not any("state_update" in n
                   for n in retention_op_names["prefill"])
    from deepspeed_tpu.monitor.xplane_reader import scope_of
    assert scope_of("jit(decode_step)/while/body/attn/state_update/x")[0] \
        == ("attn", "state_update")
    assert scope_of("jit(state_copy)/state_copy/dynamic_update_slice")[0] \
        == ("state_copy",)


# --------------------------------------------------------------------- #
# (h) a model with two classes of cache layers (PR 38): the scopes of the
# window and the full attend, the classes' span args and the snapshot
# --------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def classes_engine():
    from test_afmoe_serving import engine_of, seeded, tiny
    cfg = tiny()
    eng = engine_of(cfg, seeded(cfg), True)
    yield eng
    eng.close()


@pytest.fixture(scope="module")
def classes_op_names(classes_engine):
    eng = classes_engine
    G, W = eng.dp, eng.allocator.table_width
    key, temp = eng._next_key(), np.float32(0.0)
    return {
        "decode": _op_names(eng._decode_fn, eng._params, *eng._pools(),
                            eng._no_fetch, eng.last_tokens,
                            np.ones(eng.max_slots, bool), eng.lengths,
                            eng.block_tables, key, temp),
        "prefill": _op_names(
            eng._prefill_fn, eng._params, *eng._pools(),
            np.zeros((G, eng.prefill_chunk), np.int32),
            np.zeros((G, W), np.int32), np.zeros(G, np.int32),
            np.zeros(G, np.int32), np.ones(G, np.int32), np.int32(1), key,
            temp)}


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("scope", [
    "embed", "attn/qkv_proj", "attn/kv_write", "attn/attend_window",
    "attn/attend_full", "attn/out_proj", "mlp", "moe/router",
    "moe/dispatch", "moe/experts", "moe/combine", "moe/shared", "lm_head",
    "sample"])
def test_classes_program_carries_scope(classes_op_names, program, scope):
    assert any(f"/{scope}" in n for n in classes_op_names[program]), \
        (program, scope)


def test_the_readers_list_names_the_classes_scopes_and_args():
    from deepspeed_tpu.monitor.xplane_reader import (SCOPES, SPAN_ARGS,
                                                     scope_of, span_args)
    assert {"attend_window", "attend_full", "qkv_proj", "out_proj"} \
        <= set(SCOPES)
    assert scope_of("jit(decode_step)/attn/attend_window/pallas_call")[0] \
        == ("attn", "attend_window")
    # the args a class adds go by the names the MODEL declares
    assert span_args("decode") == SPAN_ARGS["decode"]
    assert "context_tokens_in_reach" in SPAN_ARGS["decode"]
    assert set(span_args("decode", ("full", "window"))) \
        - set(SPAN_ARGS["decode"]) == {
            "full_blocks_live", "window_blocks_live",
            "window_blocks_returned", "full_blocks_returned"}
    assert set(span_args("prefill", ("full", "window"))) \
        - set(SPAN_ARGS["prefill"]) == {
            "cached_tokens_full", "cached_tokens_window",
            "full_blocks_returned", "window_blocks_returned",
            "context_tokens_in_reach_full", "context_tokens_in_reach_window",
            "attend_rows_read_full", "attend_rows_read_window"}
    assert not any("full" in a or "window" in a
                   for args in SPAN_ARGS.values() for a in args)


def test_decode_and_prefill_spans_carry_the_classes(tmp_path, classes_engine):
    """Every class's blocks in use and returned ride the ``decode`` span
    with the key rows in reach; a ``prefill`` span says what each class
    took from its cache; ``snapshot()`` carries the classes' totals."""
    from deepspeed_tpu.monitor.xplane_reader import span_args
    eng = classes_engine
    names = [c.name for c in eng.served.cache_classes]
    rng = np.random.default_rng(0)
    doc = rng.integers(0, 128, size=40, dtype=np.int32)
    eng.serve([Request(rid=-1, prompt=doc, max_new_tokens=1, arrival_s=0.0)])
    reqs = [Request(rid=i, prompt=np.concatenate(
        [doc, rng.integers(0, 128, size=6 + i, dtype=np.int32)]),
        max_new_tokens=12, arrival_s=0.0) for i in range(2)]
    report = {}
    found = _session(tmp_path, lambda: report.update(eng.serve(reqs)))
    dispatched = _dispatched(found)
    assert dispatched and all(set(a) <= set(span_args("decode", names))
                              for a in dispatched)
    for a in dispatched:
        assert a["full_blocks_live"] > a["window_blocks_live"] > 0
        assert a["full_blocks_returned"] == 0
        # one full layer reads all of a stream, four window layers 8 each
        assert a["context_tokens_in_reach"] < 5 * a["context_tokens"]
        assert a["context_tokens_in_reach"] \
            == a["context_tokens"] + 4 * 8 * a["active"]
    assert dispatched[-1]["window_blocks_returned"] \
        > dispatched[0]["window_blocks_returned"]
    first = found["prefill"][0][2]
    assert set(first) <= set(span_args("prefill", names))
    assert first["cached_tokens_full"] == 40 * first["slots"]
    assert first["cached_tokens_window"] == 8 * first["slots"]
    classes = report["cache_classes"]
    assert classes["window"]["returned"] \
        == dispatched[-1]["window_blocks_returned"]
    assert classes["full"]["reach"] is None and classes["window"]["reach"] == 8



# --------------------------------------------------------------------- #
# (i) a model that keeps K/V pages BESIDE a state a stream (PR 45): the conv
# layers' scopes, the page copy's own program, the span args of an
# admission across kinds and the snapshot
# --------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def kinds_engine():
    import jax.numpy as jnp
    from deepspeed_tpu.models.lfm2 import CONV, FULL, Lfm2Config, lfm2_init
    cfg = Lfm2Config(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=5, num_dense_layers=1,
        num_attention_heads=4, num_key_value_heads=2, num_experts=8,
        num_experts_per_tok=2, layer_types=(CONV, FULL, CONV, CONV, CONV),
        max_position_embeddings=256, dtype=jnp.float32,
        initializer_range=0.08)
    eng = InferenceEngine(
        cfg, lfm2_init(jax.random.PRNGKey(0), cfg),
        config={"inference": {"max_slots": 4, "max_seq_len": 128,
                              "prefill_chunk": 8, "block_size": 4,
                              "num_blocks": {"full": 96, "conv": 12},
                              "paged_kernel": True}},
        mesh=build_mesh(devices=jax.devices()[:1]))
    yield eng
    eng.close()


@pytest.fixture(scope="module")
def kinds_op_names(kinds_engine):
    eng = kinds_engine
    G, W = eng.dp, eng.allocator.table_width
    key, temp = eng._next_key(), np.float32(0.0)
    return {
        "decode": _op_names(eng._decode_fn, eng._params, *eng._pools(),
                            eng._no_fetch, eng.last_tokens,
                            np.ones(eng.max_slots, bool), eng.lengths,
                            eng.block_tables, key, temp),
        "prefill": _op_names(
            eng._prefill_fn, eng._params, *eng._pools(),
            np.zeros((G, eng.prefill_chunk), np.int32),
            np.zeros((G, W), np.int32), np.zeros(G, np.int32),
            np.zeros(G, np.int32), np.ones(G, np.int32), np.int32(1), key,
            temp),
        "copy": _op_names(eng._copy_fn, *eng._pools(),
                          np.zeros(G, np.int32), np.ones(G, np.int32))}


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("scope", [
    "embed", "conv/conv_in_proj", "conv/conv_mix", "conv/conv_out_proj",
    "attn/qkv_proj", "attn/kv_write", "attn/attend_full", "attn/out_proj",
    "mlp", "moe/router", "moe/dispatch", "moe/experts", "moe/combine",
    "lm_head", "sample"])
def test_kinds_program_carries_scope(kinds_op_names, program, scope):
    assert any(f"/{scope}" in n for n in kinds_op_names[program]), \
        (program, scope)


def test_the_page_copy_is_a_program_of_its_own_scope(kinds_op_names,
                                                     kinds_engine):
    assert any("/state_copy" in n for n in kinds_op_names["copy"])
    assert kinds_engine._copy_fn.__name__ == "state_copy"
    assert not any("/moe/shared" in n for n in kinds_op_names["decode"])


def test_the_readers_list_names_the_kinds_scopes_and_args():
    from deepspeed_tpu.monitor.xplane_reader import (SCOPES, SPAN_ARGS,
                                                     scope_of, span_args)
    assert {"conv", "conv_in_proj", "conv_mix", "conv_out_proj",
            "state_copy", "attend_full"} <= set(SCOPES)
    assert scope_of("jit(decode_step)/conv/conv_mix/scatter")[0] \
        == ("conv", "conv_mix")
    assert "prefix_lost_to_kind_tokens" in SPAN_ARGS["prefill"]
    assert {"state_pages_live", "filter_rows_in_place"} \
        <= set(SPAN_ARGS["decode"])
    assert set(span_args("decode", ("full", "conv"))) \
        - set(SPAN_ARGS["decode"]) == {
            "full_blocks_live", "conv_blocks_live", "full_blocks_returned",
            "conv_blocks_returned"}


def test_decode_and_prefill_spans_carry_both_kinds(tmp_path, kinds_engine):
    """A ``prefill`` span of an admission across kinds says what each class
    took from its cache, what the state class resumed from, left and
    copied, and what the pages had for nothing; a ``decode`` span carries
    the pages in use beside the state pages rewritten, and counts key rows
    in the attention layers only; ``snapshot()`` carries the state's sums
    and the allocator's totals."""
    from deepspeed_tpu.monitor.xplane_reader import span_args
    eng = kinds_engine
    names = [c.name for c in eng.served.cache_classes]
    assert names == ["full", "conv"]
    rng = np.random.default_rng(0)
    doc = rng.integers(0, 128, size=41, dtype=np.int32)
    eng.reset_serving_stats()
    eng.serve([Request(rid=-1, prompt=doc, max_new_tokens=1, arrival_s=0.0)])
    reqs = [Request(rid=i, prompt=np.concatenate(
        [doc, rng.integers(0, 128, size=10 + i, dtype=np.int32)]),
        max_new_tokens=8, arrival_s=0.0) for i in range(2)]  # (a page: 8)
    report = {}
    found = _session(tmp_path, lambda: report.update(eng.serve(reqs)))
    dispatched = _dispatched(found)
    assert dispatched and all(set(a) <= set(span_args("decode", names))
                              for a in dispatched)
    for a in dispatched:
        assert a["state_pages_live"] == a["active"] \
            == a["conv_blocks_live"]
        # (64 channels: a tile the in-place kernel does not take)
        assert a["filter_rows_in_place"] == 0
        assert a["full_blocks_live"] > a["conv_blocks_live"]
        # one attention layer reads all of a stream; a state has no rows
        assert a["context_tokens_in_reach"] == a["context_tokens"]
    first = found["prefill"][0][2]
    assert set(first) <= set(span_args("prefill", names))
    page = eng.cache_specs[-1].block_nbytes()
    assert first["cached_tokens_full"] == first["cached_tokens_conv"] \
        == first["resumed_tokens"] == 40 * first["slots"]
    # every turn's snapshot frozen by its own chunk program: the one copy
    # an admission dispatches is the snapshot it resumed from
    assert first["snapshot_taken"] == first["snapshot_in_program"] \
        == first["slots"]
    assert first["state_copy_bytes"] == 2 * page * first["slots"]
    assert first["prefix_lost_to_kind_tokens"] == 0
    state = report["state"]
    assert state["resumed_tokens"] == 80 and state["snapshot_hits"] == 2
    assert state["snapshots_taken"] >= 2     # the document's + a turn's
    assert state["prefix_lost_to_kind_tokens"] == 0
    assert set(report["cache_classes"]) == {"full", "conv"}


# --------------------------------------------------------------------- #
# (j) a model whose EVERY layer keeps K/V pages and a state a stream (PR
# 48): the state-space mixer's scopes beside the attention branch's
# --------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def both_kinds_op_names():
    import jax.numpy as jnp
    from deepspeed_tpu.models.falcon_h1 import (FalconH1Config,
                                                falcon_h1_init)
    cfg = FalconH1Config(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=10, num_key_value_heads=2,
        head_dim=16, mamba_d_ssm=32, mamba_n_heads=4, mamba_d_head=8,
        mamba_d_state=16, mamba_n_groups=2, mamba_chunk_size=8,
        max_position_embeddings=256, dtype=jnp.float32)
    eng = InferenceEngine(
        cfg, falcon_h1_init(jax.random.PRNGKey(0), cfg),
        config={"inference": {"max_slots": 4, "max_seq_len": 128,
                              "prefill_chunk": 8, "block_size": 4,
                              "num_blocks": {"full": 96, "state": 12},
                              "paged_kernel": True}},
        mesh=build_mesh(devices=jax.devices()[:1]))
    G, W = eng.dp, eng.allocator.table_width
    key, temp = eng._next_key(), np.float32(0.0)
    names = {
        "decode": _op_names(eng._decode_fn, eng._params, *eng._pools(),
                            eng._no_fetch, eng.last_tokens,
                            np.ones(eng.max_slots, bool), eng.lengths,
                            eng.block_tables, key, temp),
        # (+ the snapshot's row and page: the program freezes it)
        "prefill": _op_names(
            eng._prefill_fn, eng._params, *eng._pools(),
            np.zeros((G, eng.prefill_chunk), np.int32),
            np.zeros((G, W), np.int32), np.zeros(G, np.int32),
            np.zeros(G, np.int32), np.ones(G, np.int32),
            np.zeros(G, np.int32), np.zeros(G, np.int32), np.int32(1), key,
            temp)}
    eng.close()
    return names


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("scope", [
    "embed", "attn/qkv_proj", "attn/kv_write", "attn/attend_full",
    "attn/out_proj", "ssm/ssm_in_proj", "ssm/ssm_conv", "ssm/ssm_gate_norm",
    "ssm/ssm_out_proj", "mlp", "lm_head", "sample"])
def test_both_kinds_program_carries_scope(both_kinds_op_names, program,
                                          scope):
    assert any(f"/{scope}" in n for n in both_kinds_op_names[program]), \
        (program, scope)


def test_the_state_update_and_the_scan_each_belong_to_one_program(
        both_kinds_op_names):
    names = both_kinds_op_names
    assert any("/ssm/ssm_state_update" in n for n in names["decode"])
    assert not any("/ssm_chunk_scan" in n for n in names["decode"])
    assert any("/ssm/ssm_chunk_scan" in n for n in names["prefill"])
    assert not any("/ssm_state_update" in n for n in names["prefill"])


def test_the_readers_list_names_the_state_space_scopes():
    from deepspeed_tpu.monitor.xplane_reader import SCOPES, scope_of
    assert {"ssm", "ssm_in_proj", "ssm_conv", "ssm_state_update",
            "ssm_chunk_scan", "ssm_gate_norm", "ssm_out_proj"} <= set(SCOPES)
    assert scope_of("jit(decode_step)/ssm/ssm_state_update/pallas_call")[0] \
        == ("ssm", "ssm_state_update")
    assert scope_of("jit(prefill_step)/ssm/ssm_chunk_scan/while/dot")[0] \
        == ("ssm", "ssm_chunk_scan")


# --------------------------------------------------------------------- #
# (k) a model whose latent-attention layers stand BETWEEN layers that keep
# a delta-rule state a stream (PR 52): the KDA mixer's scopes beside the
# latent sublayer's, under one ``attn``
# --------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def kimi_op_names():
    import jax.numpy as jnp
    from deepspeed_tpu.models.kimi_linear import (KimiLinearConfig,
                                                  kimi_linear_init)
    cfg = KimiLinearConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=4, num_attention_heads=4,
        num_key_value_heads=4, kda_num_heads=2, kda_head_dim=16,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, num_experts=8, held=(0, 4), num_experts_per_token=2,
        model_max_length=256, dtype=jnp.float32)
    eng = InferenceEngine(
        cfg, kimi_linear_init(jax.random.PRNGKey(0), cfg),
        config={"inference": {"max_slots": 4, "max_seq_len": 128,
                              "prefill_chunk": 8, "block_size": 4,
                              "num_blocks": {"latent": 96, "state": 12},
                              "paged_kernel": True}},
        mesh=build_mesh(devices=jax.devices()[:1]))
    G, W = eng.dp, eng.allocator.table_width
    key, temp = eng._next_key(), np.float32(0.0)
    names = {
        "decode": _op_names(eng._decode_fn, eng._params, *eng._pools(),
                            eng._no_fetch, eng.last_tokens,
                            np.ones(eng.max_slots, bool), eng.lengths,
                            eng.block_tables, key, temp),
        # (+ the snapshot's row and page: the program freezes it)
        "prefill": _op_names(
            eng._prefill_fn, eng._params, *eng._pools(),
            np.zeros((G, eng.prefill_chunk), np.int32),
            np.zeros((G, W), np.int32), np.zeros(G, np.int32),
            np.zeros(G, np.int32), np.ones(G, np.int32),
            np.zeros(G, np.int32), np.zeros(G, np.int32), np.int32(1), key,
            temp)}
    eng.close()
    return names


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("scope", [
    "embed", "attn/kda_proj", "attn/kda_conv", "attn/kda_gate",
    "attn/kda_out", "attn/latent_proj", "attn/kv_write", "attn/attend",
    "mlp", "moe/router", "moe/dispatch", "moe/experts", "moe/combine",
    "moe/shared", "lm_head", "sample"])
def test_kimi_program_carries_scope(kimi_op_names, program, scope):
    assert any(f"/{scope}" in n for n in kimi_op_names[program]), \
        (program, scope)


def test_the_delta_rule_update_and_its_chunked_form_each_belong_to_one_program(
        kimi_op_names):
    names = kimi_op_names
    assert any("/attn/kda_update" in n for n in names["decode"])
    assert not any("/kda_chunk" in n for n in names["decode"])
    assert any("/attn/kda_chunk" in n for n in names["prefill"])
    assert not any("/kda_update" in n for n in names["prefill"])


def test_the_readers_list_names_the_kda_scopes():
    from deepspeed_tpu.monitor.xplane_reader import SCOPES, scope_of
    assert {"kda_proj", "kda_conv", "kda_gate", "kda_update", "kda_chunk",
            "kda_out"} <= set(SCOPES)
    assert scope_of("jit(decode_step)/attn/kda_update/pallas_call")[0] \
        == ("attn", "kda_update")
    assert scope_of("jit(prefill_step)/attn/kda_chunk/while/dot")[0] \
        == ("attn", "kda_chunk")


# --------------------------------------------------------------------- #
# (k') K/V pages under an output gate beside delta-rule states (PR 64): the
# gate's scope, both programs' KDA scopes, the decode span's context and the
# prefill span's walked rows (a chunk of two runs reads its reach twice)
# --------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def solar_engine():
    import jax.numpy as jnp
    from deepspeed_tpu.models.solar_open2 import (SolarOpen2Config,
                                                  solar_open2_init)
    cfg = SolarOpen2Config(
        vocab_size=128, hidden_size=64, moe_intermediate_size=32,
        num_hidden_layers=4, num_attention_heads=8, num_key_value_heads=1,
        head_dim=16, kda_num_heads=4, kda_head_dim=16, n_routed_experts=16,
        held=(0, 4), num_experts_per_tok=2, max_position_embeddings=512,
        dtype=jnp.float32)
    eng = InferenceEngine(
        cfg, solar_open2_init(jax.random.PRNGKey(0), cfg),
        config={"inference": {"max_slots": 2, "max_seq_len": 512,
                              "prefill_chunk": 128, "block_size": 16,
                              "num_blocks": {"full": 64, "state": 6},
                              "paged_kernel": False}},
        mesh=build_mesh(devices=jax.devices()[:1]))
    yield eng
    eng.close()


def test_solar_programs_carry_the_gate_and_the_kda_scopes(solar_engine):
    from deepspeed_tpu.monitor.xplane_reader import SCOPES, scope_of
    eng = solar_engine
    G, W = eng.dp, eng.allocator.table_width
    key, temp = eng._next_key(), np.float32(0.0)
    decode = _op_names(eng._decode_fn, eng._params, *eng._pools(),
                       eng._no_fetch, eng.last_tokens,
                       np.ones(eng.max_slots, bool), eng.lengths,
                       eng.block_tables, key, temp)
    prefill = _op_names(
        eng._prefill_fn, eng._params, *eng._pools(),
        np.zeros((G, eng.prefill_chunk), np.int32),
        np.zeros((G, W), np.int32), np.zeros(G, np.int32),
        np.zeros(G, np.int32), np.ones(G, np.int32), np.zeros(G, np.int32),
        np.zeros(G, np.int32), np.int32(1), key, temp)
    for names, own, other in ((decode, "kda_update", "kda_chunk"),
                              (prefill, "kda_chunk", "kda_update")):
        for scope in ("embed", "attn/kda_proj", "attn/kda_conv",
                      "attn/kda_gate", "attn/" + own, "attn/kda_out",
                      "attn/qkv_proj", "attn/kv_write", "attn/attend_full",
                      "attn/attn_gate", "attn/out_proj", "moe/router",
                      "moe/experts", "moe/shared", "lm_head"):
            assert any(f"/{scope}" in n for n in names), scope
        assert not any("/" + other in n for n in names)
    assert "attn_gate" in SCOPES
    assert scope_of("jit(decode_step)/attn/attn_gate/logistic")[0] \
        == ("attn", "attn_gate")


def test_solar_spans_carry_the_context_and_the_rows_walked(tmp_path,
                                                           solar_engine):
    eng = solar_engine
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, 128, size=200 + i,
                                               dtype=np.int32),
                    max_new_tokens=4, arrival_s=0.0) for i in range(2)]
    found = _session(tmp_path, lambda: eng.serve(reqs))
    dispatched = _dispatched(found)
    assert dispatched and all(a["context_tokens"] >= 200 * a["active"]
                              for a in dispatched)
    # one grouped-query layer: the key rows in reach are the context
    assert all(a["context_tokens_in_reach"] == a["context_tokens"]
               for a in dispatched)
    spans = [a for _, _, a in found["prefill"]]
    one = next(a for a in spans if a["slots"] == 1
               and a["prompt_tokens"] == 200)
    # two chunks of 128 and 72 rows; 8 query heads a K/V head x 128 rows is
    # two runs of 64: each walks its own reach
    assert one["context_tokens_in_reach_full"] == 128 + 200
    assert one["attend_rows_read_full"] == (64 + 128) + (128 + 64 + 200)
    assert "attend_rows_read_state" not in one


# --------------------------------------------------------------------- #
# (l) a model whose EVERY layer routes, from the block's input, ahead of its
# attention (PR 54): the same scopes in both programs, the router's ops
# named before the attend's, the admissions' class args and the totals
# --------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def route_ahead_engine():
    from test_smallthinker_serving import engine_of, seeded, tiny
    cfg = tiny()
    eng = engine_of(cfg, seeded(cfg), True)
    yield eng
    eng.close()


@pytest.fixture(scope="module")
def route_ahead_op_names(route_ahead_engine):
    eng = route_ahead_engine
    G, W = eng.dp, eng.allocator.table_width
    key, temp = eng._next_key(), np.float32(0.0)
    return {
        "decode": _op_names(eng._decode_fn, eng._params, *eng._pools(),
                            eng._no_fetch, eng.last_tokens,
                            np.ones(eng.max_slots, bool), eng.lengths,
                            eng.block_tables, key, temp),
        "prefill": _op_names(
            eng._prefill_fn, eng._params, *eng._pools(),
            np.zeros((G, eng.prefill_chunk), np.int32),
            np.zeros((G, W), np.int32), np.zeros(G, np.int32),
            np.zeros(G, np.int32), np.ones(G, np.int32), np.int32(1), key,
            temp)}


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("scope", [
    "embed", "attn/qkv_proj", "attn/kv_write", "attn/attend_window",
    "attn/attend_full", "attn/out_proj", "moe/router", "moe/dispatch",
    "moe/experts", "moe/combine", "lm_head", "sample"])
def test_route_ahead_program_carries_scope(route_ahead_op_names, program,
                                           scope):
    assert any(f"/{scope}" in n for n in route_ahead_op_names[program]), \
        (program, scope)


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_route_ahead_program_has_no_dense_or_shared_scope(
        route_ahead_op_names, program):
    assert not any("/mlp" in n or "/moe/shared" in n
                   for n in route_ahead_op_names[program])


def test_the_experts_run_the_relu_kernel_under_their_scope(
        route_ahead_engine):
    eng = route_ahead_engine
    text = eng._build_decode_step().lower(
        eng._params, *eng._pools(), eng._no_fetch, eng.last_tokens,
        np.ones(eng.max_slots, bool), eng.lengths, eng.block_tables,
        eng._next_key(), np.float32(0.0)).as_text(debug_info=True)
    assert "_greglu_kernel" in text and "_gswiglu_kernel" not in text


def test_prefill_spans_carry_what_the_window_returned(tmp_path,
                                                      route_ahead_engine):
    """A prompt several windows long returns ring blocks WHILE it is
    admitted; its ``prefill`` span says how many, the key rows each class's
    chunk programs could read and the live rows its LAST chunk routed;
    ``snapshot()`` carries the run's totals."""
    from deepspeed_tpu.monitor.xplane_reader import span_args
    eng = route_ahead_engine
    eng.reset_serving_stats()
    names = [c.name for c in eng.served.cache_classes]
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, 128, size=45 + i,
                                               dtype=np.int32),
                    max_new_tokens=6, arrival_s=0.0) for i in range(2)]
    report = {}
    found = _session(tmp_path, lambda: report.update(eng.serve(reqs)))
    spans = [a for _, _, a in found["prefill"]]
    assert spans and all(set(a) <= set(span_args("prefill", names))
                         for a in spans)
    for a in spans:
        # 45-46 rows in chunks of 8: blocks 0..7 leave the reach of 8
        assert a["window_blocks_returned"] == 8 * a["slots"]
        assert "full_blocks_returned" not in a
        # the counters ride the fetch of the chunk program that ENDED the
        # prompt: its live rows (45 = 5 x 8 + 5), top-3 x 8 layers
        assert a["rows"] == a["prompt_tokens"] % 8
        assert a["moe_held_pairs"] == 3 * 8 * a["rows"]
    # six chunks a prompt: the full class's two layers read 8 + 16 + ...
    # rows back, the window class's six at most 8 + 8 - 1 a chunk
    one = next(a for a in spans if a["slots"] == 1
               and a["prompt_tokens"] == 45)
    assert one["context_tokens_in_reach_full"] \
        == 2 * (8 + 16 + 24 + 32 + 40 + 45)
    assert one["context_tokens_in_reach_window"] \
        == 6 * (8 + 15 + 15 + 15 + 15 + 12)
    # (7 query heads a K/V head x 8 rows fit one run: a chunk's attend walks
    # its reach once)
    assert one["attend_rows_read_full"] == one["context_tokens_in_reach_full"]
    assert one["attend_rows_read_window"] \
        == one["context_tokens_in_reach_window"]
    dispatched = _dispatched(found)
    assert dispatched and all(set(a) <= set(span_args("decode", names))
                              for a in dispatched)
    fetched = [a for _, _, a in found["decode"] if "rows" in a]
    assert fetched and all(a["moe_held_pairs"] == 3 * 8 * a["rows"]
                           for a in fetched)
    assert report["prefill_window_blocks_returned"] \
        == sum(a["window_blocks_returned"] for a in spans)
    assert report["cached_tokens_full"] == report["cached_tokens_window"] == 0
    assert report["cache_classes"]["window"]["returned"] \
        > report["prefill_window_blocks_returned"]


# --------------------------------------------------------------------- #
# (m) sparse layers that select what they read beside Lightning layers (PR
# 59): the pooled keys' write, the selection and the per-head attend, the
# Lightning mixer's scopes, the selection's counters on the spans
# --------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def sala_engine():
    from test_minicpm_sala_serving import seeded, tiny
    cfg = tiny()
    eng = InferenceEngine(
        cfg, seeded(cfg),
        config={"inference": {"max_slots": 4, "max_seq_len": 256,
                              "prefill_chunk": 16, "block_size": 16,
                              "num_blocks": {"sparse": 64, "state": 12},
                              "paged_kernel": True}},
        mesh=build_mesh(devices=jax.devices()[:1]))
    yield eng
    eng.close()


@pytest.fixture(scope="module")
def sala_op_names(sala_engine):
    eng = sala_engine
    G, W = eng.dp, eng.allocator.table_width
    key, temp = eng._next_key(), np.float32(0.0)
    return {
        "decode": _op_names(eng._decode_fn, eng._params, *eng._pools(),
                            eng._no_fetch, eng.last_tokens,
                            np.ones(eng.max_slots, bool), eng.lengths,
                            eng.block_tables, key, temp),
        "prefill": _op_names(
            eng._prefill_fn, eng._params, *eng._pools(),
            np.zeros((G, eng.prefill_chunk), np.int32),
            np.zeros((G, W), np.int32), np.zeros(G, np.int32),
            np.zeros(G, np.int32), np.ones(G, np.int32),
            np.zeros(G, np.int32), np.zeros(G, np.int32), np.int32(1), key,
            temp)}


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("scope", [
    "embed", "attn/qkv_proj", "attn/kv_write", "attn/ck_write",
    "attn/select", "attn/attend_sparse", "attn/out_proj", "attn/la_proj",
    "attn/la_gate_norm", "attn/la_out", "mlp", "lm_head", "sample"])
def test_sala_program_carries_scope(sala_op_names, program, scope):
    assert any(f"/{scope}" in n for n in sala_op_names[program]), \
        (program, scope)


def test_the_lightning_update_and_chunk_each_belong_to_one_program(
        sala_op_names):
    names = sala_op_names
    assert any("/attn/la_state_update" in n for n in names["decode"])
    assert not any("/la_chunk" in n for n in names["decode"])
    assert any("/attn/la_chunk" in n for n in names["prefill"])
    assert not any("/la_state_update" in n for n in names["prefill"])


def test_the_readers_list_names_the_selections_scopes_and_counters():
    from deepspeed_tpu.monitor.xplane_reader import (SCOPES, SPAN_ARGS,
                                                     scope_of)
    assert {"ck_write", "select", "attend_sparse", "la_proj",
            "la_state_update", "la_chunk", "la_gate_norm",
            "la_out"} <= set(SCOPES)
    assert scope_of("jit(decode_step)/attn/select/while/dot")[0] \
        == ("attn", "select")
    assert scope_of("jit(decode_step)/attn/la_state_update/pallas_call")[0] \
        == ("attn", "la_state_update")
    counters = {"sparse_blocks_read", "sparse_blocks_in_reach",
                "sparse_read_share", "ck_rows_scored", "ck_blocks_read"}
    assert counters <= set(SPAN_ARGS["decode"])
    assert counters <= set(SPAN_ARGS["prefill"])


def test_the_selections_counters_ride_the_fetch_onto_the_spans(sala_engine):
    """A prompt past ``dense_len`` (64): its decode iterations walk 4 of 7
    blocks a sparse layer and K/V head; the report keeps the running
    means."""
    from deepspeed_tpu.inference.scheduler import Request
    eng = sala_engine
    eng.reset_serving_stats()
    rng = np.random.default_rng(5)
    report = eng.serve([Request(rid=1, prompt=rng.integers(
        0, 128, size=100, dtype=np.int32), max_new_tokens=6,
        arrival_s=0.0)])
    c = report["model_counters"]
    assert 0 < c["sparse_read_share"] < 1
    # decode at 101-106 tokens: 7 blocks in reach, 4 read
    assert c["sparse_blocks_read"] >= 4 * 2 * 2
    assert c["ck_rows_scored"] > 0
    # blocks of pooled keys the selection gathered (a running mean too)
    assert c["ck_blocks_read"] > 0


# --------------------------------------------------------------------- #
# (n) a model generated by diffusion over blocks (PR 62): the ``unmask``
# scope in the block step and in no chunk program; what a pass computed,
# committed and unmasked on the ``decode`` span; the blocks an ``emit``
# hands out and the gaps between a stream's blocks
# --------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def blocks_engine():
    from test_sdar_serving import CFG, PARAMS, engine_of
    eng = engine_of(CFG, PARAMS, kernel=True)
    yield eng
    eng.close()


@pytest.fixture(scope="module")
def blocks_op_names(blocks_engine):
    eng = blocks_engine
    G, W = eng.dp, eng.allocator.table_width
    key, temp = eng._next_key(), np.float32(0.0)
    return {
        "decode": _op_names(
            eng._decode_fn, eng._params, *eng._pools(), eng._no_fetch,
            np.zeros((eng.max_slots, eng.block_length + 1), np.int32),
            np.ones(eng.max_slots, bool), eng.lengths, eng.block_tables,
            key, temp),
        "prefill": _op_names(
            eng._prefill_fn, eng._params, *eng._pools(),
            np.zeros((G, eng.prefill_chunk), np.int32),
            np.zeros((G, W), np.int32), np.zeros(G, np.int32),
            np.zeros(G, np.int32), np.ones(G, np.int32), np.int32(0), key,
            temp)}


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("scope", [
    "embed", "attn/qkv_proj", "attn/kv_write", "attn/attend_full",
    "attn/out_proj", "moe/router", "moe/dispatch", "moe/experts",
    "moe/combine", "lm_head"])
def test_block_program_carries_scope(blocks_op_names, program, scope):
    assert any(f"/{scope}" in n for n in blocks_op_names[program]), \
        (program, scope)


def test_unmask_belongs_to_the_block_step_alone(blocks_op_names):
    """The block's update (and the proposals' ``sample`` inside it) is the
    block step's; a chunk program samples nothing and has no shared expert
    or dense layer."""
    from deepspeed_tpu.monitor.xplane_reader import (SCOPES, SPAN_ARGS,
                                                     scope_of)
    names = blocks_op_names
    assert any("/unmask" in n for n in names["decode"])
    assert any("/unmask/sample" in n for n in names["decode"])
    assert not any("/unmask" in n for n in names["prefill"])
    for program in names.values():
        assert not any("/mlp" in n or "/moe/shared" in n for n in program)
    assert "unmask" in SCOPES
    assert scope_of("jit(decode_step)/unmask/reduce_max")[0] == ("unmask",)
    assert {"block_rows", "commits", "unmasked"} <= set(SPAN_ARGS["decode"])
    assert {"blocks", "block_gaps_ms"} <= set(SPAN_ARGS["emit"])


def test_block_spans_carry_what_a_pass_did(tmp_path, blocks_engine):
    """Three requests through the scheduler: every fetched pass's rows,
    commits and unmasked positions on its ``decode`` span (2 positions a
    denoise pass of a whole block, none at a commit); every ``emit`` the
    blocks it handed out and, for a stream's later blocks, the gap since its
    block before."""
    from deepspeed_tpu.monitor.xplane_reader import span_args
    eng = blocks_engine
    eng.reset_serving_stats()
    rng = np.random.default_rng(2)
    reqs = [Request(rid=i, prompt=rng.integers(0, 120, size=n,
                                               dtype=np.int32),
                    max_new_tokens=12, arrival_s=0.0)
            for i, n in enumerate((16, 24, 9))]
    report = {}
    found = _session(tmp_path, lambda: report.update(eng.serve(reqs)))
    names = [c.name for c in eng.served.cache_classes]
    fetched = [a for _, _, a in found["decode"] if "block_rows" in a]
    assert fetched and all(set(a) <= set(span_args("decode", names))
                           for _, _, a in found["decode"])
    B = eng.block_length
    for a in fetched:
        assert a["block_rows"] % B == 0 and 0 <= a["commits"] \
            <= a["block_rows"] // B
        assert a["unmasked"] <= 2 * (a["block_rows"] // B - a["commits"])
        assert a["moe_held_pairs"] == 2 * 2 * a["block_rows"]
    blocks = sum(a["commits"] for a in fetched)
    assert blocks == 3 + 3 + 4       # 12 tokens; the third behind a tail of 1
    assert sum(a["unmasked"] for a in fetched) == 3 * 12 + 3
    emits = [a for _, _, a in found["emit"]]
    assert sum(a["blocks"] for a in emits) == blocks
    gaps = [float(g) for a in emits
            for g in str(a.get("block_gaps_ms", "")).split()]
    assert len(gaps) == blocks - 3 and min(gaps) > 0
    assert report["block_gap_ms"]["n"] == len(gaps)
    assert all(a["streams"] == a["blocks"] for a in emits)
    prefills = [a for _, _, a in found["prefill"]]
    assert prefills and all("block_rows" not in a for a in prefills)
    assert all(a["head"] == 0 for _, _, a in found["prefill_chunk"])
    # The K/V write of a pass DISPATCHED lands a block a grid step (a block
    # of 4 lies in a page of 8): rows a run = the block's length ...
    dispatched = [a for _, _, a in found["decode"] if "write_rows" in a]
    assert len(dispatched) == sum("attend_steps" in a
                                  for _, _, a in found["decode"])
    for a in dispatched:
        assert a["write_rows"] == B * a["write_runs"] > 0
    # ... and a chunk program's a page a step: the prompts of 16, 24 and 9
    # tokens as far as their last block boundary, in chunks of 16 rows.
    assert sorted((a["write_rows"], a["write_runs"])
                  for _, _, a in found["prefill_chunk"]) == \
        [(8, 1), (8, 1), (16, 2), (16, 2)]


def test_write_args_are_the_write_kernels_counts(blocks_engine, serve_engine):
    """``write_rows`` / ``write_runs`` are ``ops.paged_attention
    .write_step_counts``' of the first class's pages: a block a run where a
    model of blocks' block lies in a page, a page a run in a chunk from
    mid-page, a row a run in a decode step of a model of tokens."""
    from deepspeed_tpu.ops import paged_attention as pa
    eng = blocks_engine
    sp = eng.cache_spec
    kw = dict(block_size=sp.block_size, num_heads=sp.num_heads,
              head_dim=sp.head_dim)
    first, rows = np.asarray([8, 12, 40]), np.asarray([4, 4, 4])
    r, n, steps = pa.write_step_counts(first, rows, K=4, one_block=True, **kw)
    assert (r, n, steps) == (12, 3, 3)
    assert eng._write_args(4, first, rows) == {"write_rows": r,
                                                "write_runs": n}
    # a chunk of 16 rows from row 3 of a page of 8, 13 of them live: pages
    # 0 (5 rows), 1 (8) — and every page the 16 could touch is a step
    assert eng._write_args(16, np.asarray([3]), np.asarray([13])) == \
        {"write_rows": 13, "write_runs": 2}
    assert pa.write_step_counts([3], [13], K=16, **kw)[2] == 3
    tokens = serve_engine        # GPT-2: K/V pages, a row a stream a step
    assert tokens._write_args(1, np.asarray([5, 70, 9]), np.ones(3, int)) \
        == {"write_rows": 3, "write_runs": 3}
