"""The start-up ledger (``deepspeed_tpu/monitor/startup.py``): rows of what
a process built before it served, from JAX's own compile events.

- a jitted toy built cold, then from a warm persistent cache: ``compiled``
  then ``compile_cache``, every stage's seconds above 0;
- a tiny serving engine at two prefill widths, started twice in one cache
  directory: ``decode_step``, two ``prefill_step`` rows (the narrower one
  a ``kept_executable`` at the second start) and the copy program, all
  ``own``; the second start inside a profiler session, so the spans and
  the marker are read back with their args;
- a tiny training engine: one ``train_step`` row, the timeline's ``built``
  agrees, a retrace after start-up is a named row with telemetry off;
- the sentinel's seconds are the rows'; the list is bounded; an idle gap
  of a capture is named by the build that overlaps it.
"""
import dataclasses
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference import InferenceEngine, Request
from deepspeed_tpu.models.gpt2 import (GPT2_CONFIGS, gpt2_init,
                                       gpt2_loss_fn)
from deepspeed_tpu.monitor import startup, xplane_reader
from deepspeed_tpu.monitor.recompile import RecompileSentinel
from deepspeed_tpu.monitor.training import COL
from deepspeed_tpu.parallel.topology import build_mesh

CFG = dataclasses.replace(GPT2_CONFIGS["gpt2-tiny"], dtype=jnp.float32,
                          max_seq_length=512)


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    """A NEW persistent compile cache for this module's builds, every
    entry kept; the process's own settings come back afterwards."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    path = str(tmp_path_factory.mktemp("compile_cache"))
    for k, v in zip(keys, (path, 0, -1)):
        jax.config.update(k, v)
    cc.reset_cache()
    yield path
    for k, v in before.items():
        jax.config.update(k, v)
    cc.reset_cache()


def _since(t0):
    """The rows begun at or after ``t0`` (``startup.now()``), in the order
    they were written.  (By time, not by index: a test process that has
    built thousands of programs holds only the ledger's first and latest
    ``KEPT`` rows, and its length stands still.)"""
    return [r for r in startup.rows() if r["start_s"] >= t0]


def _builds(t0, program=None):
    return [r for r in _since(t0) if r["kind"] == "program_build"
            and (program is None or r["program"] == program)]


def test_the_first_rows_are_the_box_and_the_import():
    first = startup.rows()[:2]
    assert [r["kind"] for r in first] == ["before_program", "package_import"]
    assert first[0]["start_s"] == 0.0
    assert first[0]["end_s"] == first[1]["start_s"] > 0.0
    assert first[1]["end_s"] > first[1]["start_s"]
    assert startup.now() > first[1]["end_s"]


def test_cold_then_warm_cache(cache_dir):
    def toy():
        def startup_toy(x):                # (nested: sin, multiply)
            return jnp.sin(x) * jax.jit(lambda y: y + 1)(x)
        return jax.jit(startup_toy)
    n = startup.now()
    own = startup.own_builds()
    x = jnp.ones((4, 4))
    toy()(x)
    toy()(x)              # another function object: jit's own cache misses
    cold, warm = _builds(n, "startup_toy")
    assert (cold["source"], warm["source"]) == ("compiled", "compile_cache")
    for r in (cold, warm):
        assert r["own"] == 0 and r["end_s"] > r["start_s"]
        assert min(r["trace_s"], r["lower_s"], r["backend_s"]) > 0
        # the three stages lie inside the row, one after the other
        assert r["trace_s"] + r["lower_s"] + r["backend_s"] <= \
            r["end_s"] - r["start_s"] + 1e-6
    # functions traced inside its trace made no row of their own
    assert not [r for r in _builds(n) if r["program"] in ("sin", "<lambda>")]
    assert startup.own_builds() == own
    # a steady call builds nothing
    f = toy()
    f(x)
    n = startup.now()
    f(x)
    assert not _since(n)


# --------------------------------------------------------------------- #
# a serving engine, started twice
# --------------------------------------------------------------------- #
def _serve_start(params):
    eng = InferenceEngine(
        CFG, params, config={"inference": {
            "block_size": 16, "prefill_chunk": 256, "max_seq_len": 512,
            "max_slots": 2}}, mesh=build_mesh(devices=jax.devices()[:1]))
    rng = np.random.default_rng(0)
    same = rng.integers(0, 100, size=48, dtype=np.int32)
    report = eng.serve([Request(rid=i, prompt=same, max_new_tokens=3,
                                arrival_s=0.0) for i in range(2)])
    return eng, report


@pytest.fixture(scope="module")
def two_starts(cache_dir, tmp_path_factory):
    params = gpt2_init(jax.random.PRNGKey(0), CFG)
    t0 = startup.now()
    _serve_start(params)
    t1 = startup.now()
    trace_dir = str(tmp_path_factory.mktemp("start_prof"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        eng, report = _serve_start(params)
    finally:
        jax.profiler.stop_trace()
    return eng, report, [r for r in _since(t0) if r["start_s"] < t1], \
        _since(t1), trace_dir


def test_a_serving_start_names_its_programs(two_starts):
    eng, _, first, second, _ = two_starts
    assert eng.prefill_widths == (128, 256)
    copy = eng.allocator.copy_program[0]
    for start in (first, second):
        own = [r for r in start if r["kind"] == "program_build" and r["own"]]
        assert sorted(r["program"] for r in own) == sorted(
            ["decode_step", "prefill_step", "prefill_step", copy])
        assert [r["width"] for r in own if r["program"] == "prefill_step"] \
            == [256, 128]                       # widest first
    assert {r["source"] for r in first if r["kind"] == "program_build"
            and r["own"]} == {"compiled"}
    by = {(r["program"], r.get("width")): r for r in second
          if r["kind"] == "program_build" and r["own"]}
    assert by["prefill_step", 256]["source"] == "compile_cache"
    kept = by["prefill_step", 128]
    assert kept["source"] == "kept_executable" and kept["bytes"] > 0
    assert kept["trace_s"] == kept["lower_s"] == 0.0 < kept["backend_s"]
    assert by["decode_step", None]["source"] == "compile_cache"
    assert by["decode_step", None]["trace_s"] > 0


def test_engine_init_and_traffic_rows(two_starts):
    eng, report, first, _, _ = two_starts
    kinds = [r["kind"] for r in first if r["kind"] != "program_build"]
    assert kinds == ["place_params", "allocate_cache", "engine_init",
                     "warm_prefill_widths", "engine_traffic"]
    init = next(r for r in first if r["kind"] == "engine_init")
    assert init["mode"] == "serving"
    assert init["param_bytes"] == eng.param_bytes > 0
    assert init["cache_bytes"] == sum(sp.nbytes() for sp in eng.cache_specs)
    for child in ("place_params", "allocate_cache"):
        c = next(r for r in first if r["kind"] == child)
        assert c["parent"] == "engine_init"
        assert init["start_s"] <= c["start_s"] <= c["end_s"] <= init["end_s"]
    traffic = next(r for r in first if r["kind"] == "engine_traffic")
    assert (traffic["mode"], traffic["requests"]) == ("serving", 2)
    assert traffic["prompt_tokens"] == 96 and traffic["iterations"] >= 2
    # the widths' build and decode_step's lie inside the serve() call
    for r in first:
        if r["kind"] == "warm_prefill_widths" or \
                r.get("program") == "decode_step":
            assert traffic["start_s"] <= r["start_s"] <= r["end_s"] \
                <= traffic["end_s"]
    snap = report["startup"]
    # (the process's first token: an earlier test's, where one served)
    assert 0 < snap["first_useful_s"] <= traffic["end_s"] < snap["now_s"]
    assert snap["by_kind"]["engine_init"]["n"] >= 2
    assert snap["by_program"]["decode_step"]["own"] == 1
    # the reports carry the summary; the rows are ``snapshot()``'s
    assert "rows" not in snap and "rows" not in \
        eng._report_extra()["startup"]
    assert startup.snapshot()["rows"][-1]["kind"] == "engine_traffic"


def test_the_spans_and_the_marker_are_in_a_profile(two_starts):
    *_, second, trace_dir = two_starts
    path = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[-1]
    xspace = xplane_reader.read_xspace(path)
    found = {}
    for plane in xspace.values():
        for events in plane["lines"].values():
            for mid, start, dur, stats in events:
                name = plane["metadata"].get(mid, ("",))[0]
                if name in xplane_reader.SPANS:
                    found.setdefault(name, []).append((
                        start, dur,
                        xplane_reader.event_args(stats, plane["stat_names"])))
    names = ("engine_init", "place_params", "allocate_cache",
             "warm_prefill_widths", "executable_load", "program_build")
    for name in names:
        assert name in found, name
        for _, _, args in found[name]:
            assert set(args) <= set(xplane_reader.SPAN_ARGS[name]), name
            assert "age_s" in args
    (_, _, init), = found["engine_init"]
    assert init["mode"] == "serving" and init["param_bytes"] > 0
    (_, _, load), = found["executable_load"]
    assert (load["program"], load["width"], load["source"]) == \
        ("prefill_step", 128, "kept_executable")
    # one clock: every span and marker gives the same offset to a ms, and
    # a build row lands where its marker was left
    offset = xplane_reader.ledger_clock_offset_ns(xspace)
    for name in names:
        for start, _, args in found[name]:
            assert start - args["age_s"] * 1e9 == pytest.approx(offset,
                                                                abs=2e6)
    marked = sorted(a["program"] for _, _, a in found["program_build"])
    built = sorted(r["program"] for r in second
                   if r["kind"] == "program_build"
                   and r["source"] != "kept_executable")
    assert marked == built


# --------------------------------------------------------------------- #
# a training engine
# --------------------------------------------------------------------- #
def test_a_training_start_and_a_retrace_after_it():
    cfg = GPT2_CONFIGS["gpt2-tiny"]
    ds = {"train_batch_size": 2, "train_micro_batch_size_per_gpu": 2,
          "gradient_accumulation_steps": 1, "bf16": {"enabled": True},
          "zero_optimization": {"stage": 2},
          "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
          "steps_per_print": 10 ** 9}
    n = startup.now()
    engine, _, _, _ = deepspeed_tpu.initialize(
        config=ds, model=gpt2_loss_fn(cfg),
        model_params=gpt2_init(jax.random.PRNGKey(1), cfg),
        mesh=build_mesh(devices=jax.devices()[:1]))
    assert not engine.telemetry.enabled

    def batch(seq):
        return np.random.default_rng(seq).integers(
            0, cfg.vocab_size, (2, seq)).astype(np.int32)
    init = [r for r in _since(n) if r["kind"] == "engine_init"]
    assert len(init) == 1 and init[0]["mode"] == "training"
    assert init[0]["param_bytes"] > 0
    shard = next(r for r in _since(n) if r["kind"] == "shard_state")
    assert init[0]["start_s"] <= shard["start_s"] <= shard["end_s"] \
        <= init[0]["end_s"]
    assert [r["own"] for r in _builds(n, "init_state")] == [1]

    n = startup.now()
    for _ in range(3):
        jax.block_until_ready(engine.train_batch(batch(33)))
    assert len(_builds(n, "train_step")) == 1
    assert engine.timeline.table()[:, COL["built"]].tolist() == [1, 0, 0]
    n = startup.now()
    engine.train_batch(batch(17))             # another shape: a retrace
    late, = _builds(n, "train_step")
    assert late["own"] == 1 and late["backend_s"] > 0
    assert engine.timeline.table()[-1, COL["built"]] == 1

    snap = startup.snapshot()
    calls = [r for r in snap["rows"] if r["kind"] == "engine_traffic"
             and r.get("mode") == "training"][-4:]
    assert [c["built"] for c in calls] == [1, 0, 0, 1]
    assert [c["step"] for c in calls] == [0, 1, 2, 3]
    assert all(c["end_s"] > c["start_s"] for c in calls)
    assert snap["first_useful_s"] <= calls[1]["start_s"]
    # the first call's build lies inside its traffic row
    first = _builds(0, "train_step")[-2]
    assert calls[0]["start_s"] <= first["start_s"] <= first["end_s"] \
        <= calls[0]["end_s"] + 1e-3
    assert "rows" not in engine._report_extra()["startup"]


# --------------------------------------------------------------------- #
# one source; bounds; gaps
# --------------------------------------------------------------------- #
def test_the_sentinel_takes_its_seconds_from_the_rows():
    sentinel = RecompileSentinel(warmup_calls=1)

    def sentinel_toy(x):
        return x * 2 + 1
    fn = sentinel.instrument("toy_step", jax.jit(sentinel_toy))
    n = startup.now()
    fn(jnp.ones(3))
    fn(jnp.ones(3))
    fn(jnp.ones(5))                             # a recompile
    rows = _builds(n, "toy_step")
    assert [r["own"] for r in rows] == [1, 1]
    assert sentinel.compile_wall_s == pytest.approx(sum(
        r["trace_s"] + r["lower_s"] + r["backend_s"] for r in rows))
    assert sentinel.compile_counts() == {"toy_step": 2}
    assert sentinel.recompile_count == 1
    # a function that is no jitted one: the call's own wall
    plain = sentinel.instrument("plain", lambda x: x)
    before = sentinel.compile_wall_s
    plain(np.ones(2))
    assert sentinel.compile_wall_s > before
    assert not _builds(n, "plain")


def test_the_list_is_bounded():
    rows = startup._Rows(4)
    for i in range(20):
        rows.add({"i": i})
    assert [r["i"] for r in rows.all()] == [0, 1, 2, 3, 16, 17, 18, 19]
    assert rows.dropped == 12
    assert startup._rows.tail.maxlen == startup.KEPT


def test_an_idle_gap_is_named_by_the_build_that_overlaps_it():
    ms = 1e6
    ops = [(1, 0.0, 2 * ms, None), (1, 2 * ms, 1 * ms, None),
           (1, 40 * ms, 1 * ms, None),            # a gap of 37 ms
           (1, 41.5 * ms, 1 * ms, None),          # 0.5 ms: under the floor
           (1, 60 * ms, 1 * ms, None)]            # a gap no build touches
    xspace = {"/device:TPU:0": {"lines": {xplane_reader.OPS_LINE: ops},
                                "metadata": {}, "stat_names": {}},
              "/host:CPU": {"lines": {}, "metadata": {}, "stat_names": {}}}
    gaps = xplane_reader.idle_gaps(xspace)
    assert [(g["start_ns"], g["end_ns"]) for g in gaps] == \
        [(3 * ms, 40 * ms), (42.5 * ms, 60 * ms)]
    ledger = [{"kind": "program_build", "program": "decode_step",
               "start_s": 100.005, "end_s": 100.030},
              {"kind": "engine_traffic", "start_s": 100.0, "end_s": 100.1}]
    # no span of the ledger in the capture: the clocks cannot be tied
    assert xplane_reader.named_idle_gaps(xspace, ledger) == gaps
    named = xplane_reader.named_idle_gaps(xspace, ledger,
                                          offset_ns=-100e9)
    assert named[0]["program"] == "decode_step"
    assert named[0]["build_s"] == pytest.approx(0.025)
    assert "program" not in named[1]
